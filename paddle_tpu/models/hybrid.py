"""Patterned language models, served and trained: every letter of a pattern is ONE
mixer — `M` a Mamba-2 state-space layer, `E` a dropless mixture of experts
with a shared expert (relu^2, or gated where its tree holds gate matrices),
`*` attention with no position term, `L` latent attention (MLA: low-rank
queries, one cached latent row a token, YaRN rotary on part of the score),
`F` a dense gated FFN — in a pre-norm residual stack, final RMSNorm, untied
head.  The residual is plain (x <- x + mixer(RMSNorm(x)); Nemotron-H:
"MEMEM*E..") or, with `hc_mult` > 1, a state of several streams that every
mixer reads a learned mix of and writes back through a doubly stochastic
matrix (manifold-constrained hyper-connections, arXiv:2512.24880; a
DeepSeek-V3-shaped layer is the two letters "LF" or "LE").

`inference.engine.LLMEngine` reaches this module the way it reaches
`models.gpt`: `init_paged_cache`, `prefill_paged` (the bucketed prompt pass)
and `serve_step_paged` (the fused step) with the same contracts, told apart
by `config.layer_pattern`.  What differs:

- The layers are of three kinds, so they are not one stacked tree under a
  `lax.scan`: `params["layers"]` is a list of per-layer trees and the pattern
  is walked statically.  No weight is ever sliced out of a stack.
- The serving state is ONE donated tree (`init_paged_cache`) of lanes of two
  kinds.  Paged lanes (`HybridConfig.paged_lanes`), read through page tables
  exactly as in `models.gpt`: "k", "v" [L_attn, P, page, KVH, hd] of the `*`
  layers, and "c" [L_latent, P, page, W] of the `L` layers — per token the
  normed latent, the one rotated key all heads share, and zeros up to whole
  128-lane tiles (`latent_lane`); no values are kept, they are columns of the
  same row.  A page's bytes, the swap/spill/restore programs and the COW copy
  take their widths from these lanes.  And per Mamba layer i a recurrent
  state indexed by SLOT, not by page: "conv.i" [slots, 3, conv_dim] (the last
  three inputs of the causal convolution) and "ssm.i" [slots, H, P, N]
  float32.  Each lane is updated where it lies (`.at[].set` on the donated
  buffer); nothing pool-sized is copied.  A pattern without `M` has no state
  a page does not hold: the engine serves it as a paged model (prefix index,
  parked pages, spill tier).
- A slot's state is zeroed by the data, not by a dispatch: a row whose
  `q_offset` is 0 has no token behind it, so the step starts it from zeros
  (the bucketed prefill always does).  Rows that are padding (t >= valid) or
  inactive (null page-table row) get dt = 0 and leave both lanes untouched.
- Besides tokens the two passes return `aux`, a small int32 vector
  (`AUX_FIELDS`) of expert-routing and state counters that the engine fetches
  in the same `device_get` as the tokens.

Training (`train_loss`, reached through `parallel.HybridParallelTrainer`) walks
the SAME parameter tree with no cache and no page table, for patterns of `L`,
`F` and `E` with one residual stream: a latent layer's projections are
`latent_qkv`'s, as served, and its attention takes the EXPANDED form (k =
[W^K c ; k_rope], v = W^V c, causal, through the flash kernels at a score
width of nope + rope against a value width of its own); the expert layer is
the dropless one, differentiated.  `num_nextn_predict_layers` = 1 adds the
DeepSeek-V3 multi-token-prediction module (`params["mtp"]`) and its loss.
`M` (the chunked scan has no backward) and `hc_mult` > 1 are refused by the
trainer.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..incubate.distributed.models.moe.dropless import COUNTERS as MOE_COUNTERS
from ..incubate.distributed.models.moe.dropless import TRAIN_COUNTERS, moe_dropless
from ..incubate.kernels.grouped_matmul import TRAIN_ROW_TILE
from ..incubate.kernels.flash_attention import flash_attention_fused
from ..incubate.kernels.paged_attention import paged_latent_attention
from ..incubate.kernels.rope import apply_rope
from ..incubate.kernels.ssm import ssm_chunk_scan, ssm_update
from . import gpt as gpt_mod

KINDS = {"M": "mamba", "E": "experts", "*": "attention", "L": "mla",
         "F": "ffn"}
# the next-n module's one layer: latent attention, then experts (the family's
# module is a whole decoder layer of the kind the model's last layers are)
MTP_PATTERN = "LE"
AUX_FIELDS = MOE_COUNTERS + ("ssm_slots_live", "ssm_state_resets",
                             "latent_tokens_written", "mla_absorbed_rows")


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
    moe_gated: bool = False         # SwiGLU experts and shared expert
    # latent attention (`L`): num_heads heads of qk_nope + qk_rope score
    # columns and v_head_dim value columns, from a q_lora_rank-wide query
    # latent and a kv_lora_rank-wide cached one; rotary on the rope columns
    # only, YaRN-scaled where `rope_scaling` is given (the published group:
    # factor, original_max_position_embeddings, beta_fast, beta_slow,
    # mscale_all_dim)
    q_lora_rank: int = 48
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    # residual streams (1: the plain residual) and the Sinkhorn iterations,
    # norm epsilon and clip of the write-back matrix
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: tuple = (-30.0, 30.0)
    # training only: multi-token-prediction modules behind the main model (0
    # or 1; DeepSeek-V3, arXiv:2412.19437 section 2.2) and the weight of their
    # loss; the step of the router's bias rule (`router_bias_step`)
    num_nextn_predict_layers: int = 0
    mtp_loss_weight: float = 0.3
    router_bias_update_rate: float = 0.001

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
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("num_nextn_predict_layers is 0 or 1")

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

    @property
    def latent_row(self) -> int:
        """Numbers a latent layer writes per token: latent and rotated key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_lane(self) -> int:
        """Width of the latent lane: `latent_row` in whole 128-lane tiles
        (what the chip keeps for a row of it in any case)."""
        return -(-self.latent_row // 128) * 128

    def paged_lanes(self) -> Dict[str, tuple]:
        """{lane: (layers, shape of one token's row)} of the lanes that are
        indexed by page.  A pattern with latent layers only has no K/V lanes;
        one with no attention at all keeps them, empty, for the pool's
        geometry."""
        lanes = {}
        if self.count("*") or not self.count("L"):
            lanes["k"] = lanes["v"] = (self.count("*"),
                                       (self.kv_heads, self.head_dim))
        if self.count("L"):
            lanes["c"] = (self.count("L"), (self.latent_lane,))
        return lanes

    def page_bytes(self, page_size: int) -> int:
        """Bytes one page holds over every paged lane and layer."""
        item = jnp.dtype(self.dtype).itemsize
        return sum(n * page_size * math.prod(row) * item
                   for n, row in self.paged_lanes().values())

    @property
    def mla_softmax_scale(self) -> float:
        return gpt_mod.yarn_softmax_scale(
            self.qk_nope_head_dim + self.qk_rope_head_dim, self.rope_scaling)

    def state_bytes_per_slot(self) -> int:
        """Bytes of recurrent state one slot holds over all Mamba layers."""
        ssm = self.mamba_num_heads * self.mamba_head_dim * \
            self.ssm_state_size * 4
        conv = (self.conv_kernel - 1) * self.conv_dim * \
            jnp.dtype(self.dtype).itemsize
        return self.count("M") * (ssm + conv)


def latent_tiny(seq_len=128, pattern="LFLELE", **kw):
    """A DeepSeek-V3-shaped toy: latent attention, one leading dense FFN,
    gated experts, four residual streams, YaRN over a short original
    context."""
    kw.setdefault("rope_scaling", dict(
        factor=4.0, original_max_position_embeddings=32, beta_fast=32.0,
        beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0))
    kw.setdefault("hc_mult", 4)
    return HybridConfig(vocab_size=256, hidden_size=64, num_layers=len(pattern),
                        num_heads=4, max_seq_len=seq_len,
                        layer_pattern=pattern, intermediate_size=96,
                        moe_gated=True, rms_norm_eps=1e-6, **kw)


def joyai_tiny(seq_len=128, pattern="LFLELE", **kw):
    """A JoyAI-LLM-Flash-shaped toy: `latent_tiny` with one plain residual
    stream, no rotary scaling, a router wider than the experts held, and the
    next-token-but-one module."""
    kw.setdefault("rope_scaling", None)
    kw.setdefault("hc_mult", 1)
    kw.setdefault("num_nextn_predict_layers", 1)
    kw.setdefault("n_routed_experts", 16)
    kw.setdefault("experts_here", 4)
    kw.setdefault("routed_scaling_factor", 2.5)
    return latent_tiny(seq_len, pattern, **kw)


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
    keys = iter(jax.random.split(key, (16 if c.hc_mult > 1 else 8) * L + 4))

    def normal(shape, s, dtype=None):
        return (jax.random.normal(next(keys), shape, jnp.float32) * s
                ).astype(dtype or c.dtype)

    def uniform(shape, b):
        return jax.random.uniform(next(keys), shape, jnp.float32, -b, b
                                  ).astype(c.dtype)

    bound = 1.0 / math.sqrt(c.conv_kernel)      # a depthwise Conv1d's default
    n = c.hc_mult

    def layer(letter):
        lp = {"norm_w": jnp.ones((D,), c.dtype)}
        if n > 1:
            # the streams' mixes: phi so that x~ phi is of order one, a
            # write-back bias that favours a stream's own row without
            # silencing the others (neither the identity nor uniform)
            lp.update(
                hc_phi=normal((n * D, 2 * n + n * n), 1.0 / math.sqrt(n * D),
                              jnp.float32),
                hc_alpha=jnp.asarray([1.0, 1.0, 0.5], jnp.float32),
                hc_b=jnp.concatenate([
                    normal((2 * n,), 1.0, jnp.float32),
                    (jnp.eye(n) + normal((n, n), 0.3, jnp.float32)
                     ).reshape(-1)]))
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
            if c.moe_gated:
                lp.update(gate_w=normal((c.experts_here, F, D), std),
                          shared_gate_w=normal((D, Fs), std))
        elif letter == "F":
            F = c.ffn_size
            lp.update(gate_w=normal((D, F), std), up_w=normal((D, F), std),
                      down_w=normal((F, D), proj))
        elif letter == "L":
            H, C, R = c.num_heads, c.kv_lora_rank, c.qk_rope_head_dim
            N, V = c.qk_nope_head_dim, c.v_head_dim
            lp.update(
                q_a_w=normal((D, c.q_lora_rank), std),
                q_norm_w=jnp.ones((c.q_lora_rank,), c.dtype),
                q_b_w=normal((c.q_lora_rank, H * (N + R)), std),
                kv_a_w=normal((D, C + R), std),
                kv_norm_w=jnp.ones((C,), c.dtype),
                kv_b_k_w=normal((H, N, C), std),        # W^K per head, [N, C]
                kv_b_v_w=normal((H, C, V), std),
                o_w=normal((H * V, D), proj))
        else:
            lp.update(qkv_w=normal((D, c.qkv_dim), std),
                      proj_w=normal((c.num_heads * c.head_dim, D), proj))
        return lp

    layers = [layer(letter) for letter in c.layer_pattern]
    params = {"wte": normal((c.vocab_size, D), std), "layers": layers,
              "lnf_w": jnp.ones((D,), c.dtype),
              "lm_head": normal((D, c.vocab_size), std)}
    if c.num_nextn_predict_layers:
        # the next-n module's own keys, so that a configuration without it
        # draws what it always drew
        keys = iter(jax.random.split(jax.random.fold_in(key, 1), 32))
        params["mtp"] = {
            "hnorm_w": jnp.ones((D,), c.dtype),
            "enorm_w": jnp.ones((D,), c.dtype),
            "eh_proj": normal((2 * D, D), std),
            "layers": [layer(letter) for letter in MTP_PATTERN],
            "norm_w": jnp.ones((D,), c.dtype)}
    return params


def init_paged_cache(config: HybridConfig, num_pages: int, page_size: int,
                     num_slots: int):
    """The one tree of serving state: the paged lanes of the attention and
    latent layers and each Mamba layer's slot-indexed lanes (see the module
    docstring)."""
    c = config
    cache = {name: jnp.zeros((layers, num_pages, page_size) + row, c.dtype)
             for name, (layers, row) in c.paged_lanes().items()}
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


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def latent_qkv(lp, h, pos, c: HybridConfig):
    """The latent layer's projections of normed h [B, T, D] at positions
    pos [B, T]: (q_nope [B, T, H, N], q_rope [B, T, H, R] rotated, row
    [B, T, latent_lane] = [RMSNorm(c_kv) | k_r rotated | 0..], what the cache
    keeps of a token)."""
    B, T, _ = h.shape
    H, C, R, N = (c.num_heads, c.kv_lora_rank, c.qk_rope_head_dim,
                  c.qk_nope_head_dim)
    sin, cos = gpt_mod.yarn_rope_tables_at(R, c.rope_theta, c.rope_scaling,
                                           pos)
    with jax.named_scope("mla.q"):
        cq = _rms(jnp.matmul(h, lp["q_a_w"]), lp["q_norm_w"], c.rms_norm_eps)
        q = jnp.matmul(cq, lp["q_b_w"]).reshape(B, T, H, N + R)
        q_nope, q_rope = q[..., :N], apply_rope(q[..., N:], sin, cos)
    with jax.named_scope("mla.kv_write"):
        ckv = jnp.matmul(h, lp["kv_a_w"])
        k_r = apply_rope(ckv[..., None, C:], sin, cos)[:, :, 0]
        row = jnp.concatenate(
            [_rms(ckv[..., :C], lp["kv_norm_w"], c.rms_norm_eps), k_r,
             jnp.zeros((B, T, c.latent_lane - c.latent_row), h.dtype)],
            axis=-1)
    return q_nope, q_rope, row


def latent_attention(lp, q_nope, q_rope, pool, page_table, q_offset, valid,
                     c: HybridConfig):
    """Absorbed attention through the latent lane and the output
    projection: q_lat = q_nope W^K, scores against the cached rows, the
    weighted latents through W^V and W^O.  pool [P, page, latent_lane]."""
    B, T, H, _ = q_nope.shape
    with jax.named_scope("mla.q"):
        q_lat = jnp.einsum("bthn,hnc->bthc", q_nope, lp["kv_b_k_w"])
        q = jnp.concatenate(
            [q_lat, q_rope, jnp.zeros((B, T, H, c.latent_lane - c.latent_row),
                                      q_lat.dtype)], axis=-1)
    with jax.named_scope("mla.attn"):
        o_lat = paged_latent_attention(q, pool, page_table, q_offset, valid,
                                       c.kv_lora_rank, c.mla_softmax_scale)
    with jax.named_scope("mla.out"):
        o = jnp.einsum("bthc,hcv->bthv", o_lat, lp["kv_b_v_w"])
        return jnp.matmul(o.reshape(B, T, H * c.v_head_dim), lp["o_w"])


def ffn_mixer(lp, h):
    """Dense SwiGLU: down(silu(gate h) * up h)."""
    g = jax.nn.silu(jnp.matmul(h, lp["gate_w"]).astype(jnp.float32))
    a = (g * jnp.matmul(h, lp["up_w"]).astype(jnp.float32)).astype(h.dtype)
    return jnp.matmul(a, lp["down_w"])


def sinkhorn(m, iters: int):
    """`iters` rounds of row- then column-normalisation of positive
    m [..., n, n]: towards a doubly stochastic matrix."""
    def body(_, m):
        m = m / jnp.sum(m, axis=-1, keepdims=True)
        return m / jnp.sum(m, axis=-2, keepdims=True)
    return jax.lax.fori_loop(0, iters, body, m)


def mhc_mixes(lp, X, c: HybridConfig):
    """A mixer's three mixes of the residual streams X [B, T, n, D], all in
    float32: (pre [B, T, n] in (0, 1), post [B, T, n] in (0, 2), res
    [B, T, n, n] doubly stochastic after `hc_sinkhorn_iters` rounds)."""
    B, T, n, D = X.shape
    f32 = jnp.float32
    x = X.astype(f32).reshape(B, T, n * D)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + c.hc_eps)
    m = jnp.dot(x, lp["hc_phi"].astype(f32),
                precision=jax.lax.Precision.HIGHEST)
    a, b = lp["hc_alpha"].astype(f32), lp["hc_b"].astype(f32)
    pre = jax.nn.sigmoid(a[0] * m[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * m[..., n:2 * n] + b[n:2 * n])
    res = (a[2] * m[..., 2 * n:] + b[2 * n:]).reshape(B, T, n, n)
    res = sinkhorn(jnp.exp(jnp.clip(res, *c.hc_res_clamp)),
                   c.hc_sinkhorn_iters)
    return pre, post, res


def _sum_aux(parts, live, resets, written, rows):
    """The `AUX_FIELDS` vector from the expert layers' counters and the
    passes' own."""
    def over(name, fn):
        vals = [p[name] for p in parts]
        return fn(jnp.stack(vals)) if vals else jnp.zeros((), jnp.int32)
    return jnp.stack([
        over("moe_pairs_here", jnp.sum), over("moe_pairs_away", jnp.sum),
        over("moe_experts_touched", jnp.sum), over("moe_load_max", jnp.max),
        live.astype(jnp.int32), resets.astype(jnp.int32),
        written.astype(jnp.int32), rows.astype(jnp.int32)]).astype(jnp.int32)


def _walk(params, x, cache, c: HybridConfig, real, mamba, attention, latent):
    """The layer loop of both passes: a static walk of the pattern.
    `mamba(lp, h, cache, i)`, `attention(lp, h, cache, i)` and
    `latent(lp, h, cache, i)` return (mixer output, cache); the expert layer
    and the dense FFN need no state.  The residual rule is the plain one, or
    for `hc_mult` streams: x [B, T, D] is repeated into X [B, T, n, D], every
    mixer reads h = pre X, and X <- res X + post^T y; the streams are summed
    at the end.  Returns (x [B, T, D], cache, expert counters per E layer)."""
    B, T, D = x.shape
    n = c.hc_mult
    if n > 1:
        x = jnp.broadcast_to(x[:, :, None, :], (B, T, n, D))
    seen = dict.fromkeys(KINDS, 0)
    counters = []
    for letter, lp in zip(c.layer_pattern, params["layers"]):
        i = seen[letter]
        seen[letter] += 1
        with jax.named_scope(KINDS[letter]):
            if n > 1:
                with jax.named_scope("mhc"):
                    pre, post, res = mhc_mixes(lp, x, c)
                    h = jnp.einsum("btn,btnd->btd", pre, x.astype(jnp.float32)
                                   ).astype(x.dtype)
            else:
                h = x
            h = _norm(h, lp["norm_w"], c)
            if letter == "M":
                y, cache = mamba(lp, h, cache, i)
            elif letter == "E":
                y, ctr = moe_dropless(lp, h.reshape(B * T, D), c,
                                      real.reshape(B * T))
                y = y.reshape(B, T, D)
                counters.append(ctr)
            elif letter == "F":
                y = ffn_mixer(lp, h)
            elif letter == "L":
                y, cache = latent(lp, h, cache, i)
            else:
                y, cache = attention(lp, h, cache, i)
            if n > 1:
                with jax.named_scope("mhc"):
                    x = (jnp.einsum("btij,btjd->btid", res,
                                    x.astype(jnp.float32)) +
                         post[..., None] * y.astype(jnp.float32)[:, :, None]
                         ).astype(x.dtype)
            else:
                x = x + y
    if n > 1:
        x = jnp.sum(x.astype(jnp.float32), axis=2).astype(x.dtype)
    return x, cache, counters


def _geometry(cache, c: HybridConfig):
    """(pages, page size) of the paged lanes."""
    return cache[next(iter(c.paged_lanes()))].shape[1:3]


def _write_rows(lane, rows, off, new):
    """`new` [B, T, ...] written token by token into a paged lane [L, P,
    page, ...] at flat rows [B, T] (layer * P + page) and offsets [B, T]."""
    flat = lane.reshape((-1,) + lane.shape[2:])
    return flat.at[rows, off].set(new)


def prefill_paged(params, input_ids, config: HybridConfig, cache, pages,
                  length, slots):
    """Bucketed prefill (`gpt.prefill_paged`'s contract, plus `slots` [B]:
    where each prompt's recurrent state is kept).  The state starts from
    zeros and is written as it stands after position length - 1: bucket
    padding moves neither lane.  A latent layer stays in the absorbed form
    (the rows it has just written, read back through the paged kernel with
    q_offset 0).  Returns (logits [B, V] at the last real position, cache,
    aux)."""
    c = config
    B, Sb = input_ids.shape
    H, KVH, hd = c.num_heads, c.kv_heads, c.head_dim
    P, page = _geometry(cache, c)
    real = jnp.arange(Sb)[None, :] < length[:, None]
    # rows are written token by token, padding to the null page, as the
    # fused step writes them: a whole-page write of [page, KVH, hd] windows
    # at KVH = 2 makes the compiler re-lay the pool out and back
    pidx = jnp.where(real, jnp.take(pages, jnp.arange(Sb) // page, axis=1), 0)
    off = jnp.broadcast_to(jnp.arange(Sb) % page, (B, Sb))
    pos = jnp.broadcast_to(jnp.arange(Sb), (B, Sb))
    zero = jnp.zeros((B,), jnp.int32)
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
        new = {n: _write_rows(cache[n], rows, off, a).reshape(cache[n].shape)
               for n, a in (("k", k), ("v", v))}
        k = jnp.repeat(k, H // KVH, axis=2)
        v = jnp.repeat(v, H // KVH, axis=2)
        attn = flash_attention_fused(q, k, v, causal=True)
        return jnp.matmul(attn.reshape(B, Sb, H * hd), lp["proj_w"]), \
            dict(cache, **new)

    def latent(lp, h, cache, i):
        q_nope, q_rope, row = latent_qkv(lp, h, pos, c)
        flat = _write_rows(cache["c"], i * P + pidx, off, row)
        y = latent_attention(lp, q_nope, q_rope, flat, pages + i * P, zero,
                             length, c)
        return y, dict(cache, c=flat.reshape(cache["c"].shape))

    x, cache, counters = _walk(params, x, cache, c, real, mamba, attention,
                               latent)
    x = _norm(x[jnp.arange(B), length - 1], params["lnf_w"], c)
    has_m, has_l = int(c.count("M") > 0), int(c.count("L") > 0)
    aux = _sum_aux(counters, jnp.asarray(B * has_m), jnp.asarray(B * has_m),
                   jnp.sum(length) * has_l, jnp.sum(length) * has_l)
    return gpt_mod.head_logits(x, params, c), cache, aux


def _step_hidden(params, tokens, cache, page_table, q_offset, valid,
                 c: HybridConfig):
    """The fused step's and the chunk pass's layers: T tokens a slot at
    positions q_offset + t, through the page table and the state lanes.
    Returns (x [B, T, D] before the final norm, cache, aux)."""
    from ..incubate.kernels.paged_attention import paged_prefill_attention
    B, T = tokens.shape
    H, hd = c.num_heads, c.head_dim
    P, page = _geometry(cache, c)
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
        flat = {n: _write_rows(cache[n], base + pidx, off, a)
                for n, a in (("k", k), ("v", v))}
        attn = paged_prefill_attention(q, flat["k"], flat["v"],
                                       page_table + base, q_offset, n_real)
        return jnp.matmul(attn.reshape(B, T, H * hd), lp["proj_w"]), \
            dict(cache, **{n: a.reshape(cache[n].shape)
                           for n, a in flat.items()})

    def latent(lp, h, cache, i):
        q_nope, q_rope, row = latent_qkv(lp, h, pos, c)
        flat = _write_rows(cache["c"], i * P + pidx, off, row)
        y = latent_attention(lp, q_nope, q_rope, flat, page_table + i * P,
                             q_offset, n_real, c)
        return y, dict(cache, c=flat.reshape(cache["c"].shape))

    x, cache, counters = _walk(params, x, cache, c, real, mamba, attention,
                               latent)
    has_m, has_l = int(c.count("M") > 0), int(c.count("L") > 0)
    aux = _sum_aux(counters, jnp.sum(active) * has_m,
                   jnp.sum(active & fresh) * has_m,
                   jnp.sum(jnp.where(active, q_offset + n_real, 0)) * has_l,
                   jnp.sum(n_real) * has_l)
    return x, cache, aux


def prefill_chunk_paged(params, input_ids, config: HybridConfig, cache,
                        page_table, q_offset, valid):
    """A chunk of a prompt behind a prefix hit (`gpt.prefill_chunk_paged`'s
    contract; one more result, `aux`): the engine reaches it only for a
    pattern without recurrent state.  Returns (logits [B, V] at chunk index
    valid - 1, cache, aux)."""
    c = config
    x, cache, aux = _step_hidden(params, input_ids, cache, page_table,
                                 q_offset, valid, c)
    x = _norm(x[jnp.arange(x.shape[0]), valid - 1], params["lnf_w"], c)
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
    c = config
    B, T = tokens.shape
    x, cache, aux = _step_hidden(params, tokens, cache, page_table, q_offset,
                                 valid, c)
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
    # no draft ever rides this step (speculation is refused for a patterned
    # configuration: a rejected draft would need the state rolled back)
    accept = jnp.zeros((B,), jnp.int32)
    return out, accept, cache, key, aux


# ---------------------------------------------------------------------------
# training: the same parameter tree, no cache, no page table
# ---------------------------------------------------------------------------

# Held token-expert pairs an expert layer gathers in a training step, as a
# multiple of what a router with even loads sends here (N k E_here / E_total):
# the layer's static `pair_bound`.  Pairs past it are counted, never hidden.
TRAIN_PAIR_SLACK = 2.0
# rows of the head's logits alive at once in the loss
LOSS_BLOCK = 4096


def train_pair_bound(n_tokens: int, c: HybridConfig) -> int:
    pairs = n_tokens * c.num_experts_per_tok
    even = pairs * c.experts_here / c.n_routed_experts
    return min(pairs, -(-int(TRAIN_PAIR_SLACK * even) // TRAIN_ROW_TILE)
               * TRAIN_ROW_TILE)


def latent_attention_expanded(lp, q_nope, q_rope, row, c: HybridConfig):
    """Causal attention of a whole sequence in the EXPANDED form, and the
    output projection: k_h = [W^K_h c ; k_rope] (the rotated key shared by
    all heads), v_h = W^V_h c, from `latent_qkv`'s results; the score is
    nope + rope wide, the values `v_head_dim`."""
    B, T, H, _ = q_nope.shape
    C, R = c.kv_lora_rank, c.qk_rope_head_dim
    with jax.named_scope("mla.kv"):
        ckv, k_r = row[..., :C], row[..., C:C + R]
        k_nope = jnp.einsum("btc,hnc->bthn", ckv, lp["kv_b_k_w"])
        v = jnp.einsum("btc,hcv->bthv", ckv, lp["kv_b_v_w"])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_r[:, :, None, :], (B, T, H, R))],
            axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
    with jax.named_scope("mla.attn"):
        o = flash_attention_fused(q, k, v, causal=True,
                                  scale=c.mla_softmax_scale)
    with jax.named_scope("mla.out"):
        return jnp.matmul(o.reshape(B, T, H * c.v_head_dim), lp["o_w"])


def _train_layer(letter: str, c: HybridConfig, lp, x):
    """One mixer of the training walk with its plain residual: x [B, S, D]
    -> (x', the expert layer's counters or {})."""
    B, S, D = x.shape
    ctr = {}
    with jax.named_scope(KINDS[letter]):
        h = _norm(x, lp["norm_w"], c)
        if letter == "L":
            pos = jnp.broadcast_to(jnp.arange(S), (B, S))
            y = latent_attention_expanded(lp, *latent_qkv(lp, h, pos, c), c)
        elif letter == "F":
            y = ffn_mixer(lp, h)
        elif letter == "E":
            y, ctr = moe_dropless(lp, h.reshape(B * S, D), c,
                                  jnp.ones((B * S,), bool),
                                  pair_bound=train_pair_bound(B * S, c),
                                  row_tile=TRAIN_ROW_TILE)
            y = y.reshape(B, S, D)
        else:
            raise ValueError(f"no training pass for a {KINDS[letter]} layer")
        return x + y, ctr


def _train_walk(pattern: str, layers, x, c: HybridConfig, remat: bool):
    """(x after the layers, [counters of each E layer])."""
    from ..incubate.kernels.flash_attention import remat_policy_save_attention
    counters = []
    for letter, lp in zip(pattern, layers):
        fn = functools.partial(_train_layer, letter, c)
        if remat:
            fn = jax.checkpoint(fn, policy=remat_policy_save_attention())
        x, ctr = fn(lp, x)
        if ctr:
            counters.append(ctr)
    return x, counters


def _blocked_ce(x, norm_w, head, labels, c: HybridConfig):
    """Mean cross-entropy (float32) of head(RMSNorm(x)) against labels
    [B, S] (< 0: ignored), `LOSS_BLOCK` positions at a time under
    `jax.checkpoint`: the logits never stand whole, forward or backward."""
    D = x.shape[-1]
    rows, lab = x.reshape(-1, D), labels.reshape(-1)
    n = rows.shape[0]
    block = LOSS_BLOCK if n % LOSS_BLOCK == 0 else n

    def body(carry, xl):
        xx, ll = xl
        logits = jnp.matmul(_norm(xx, norm_w, c), head,
                            preferred_element_type=jnp.float32)
        ls, cnt = gpt_mod._ce_sums(logits, ll)
        return (carry[0] + ls, carry[1] + cnt), None

    zero = jnp.zeros((), jnp.float32)
    (ls, cnt), _ = jax.lax.scan(
        jax.checkpoint(body), (zero, zero),
        (rows.reshape(-1, block, D), lab.reshape(-1, block)))
    return ls / jnp.maximum(cnt, 1.0)


def mtp_labels(labels):
    """The next-n module's targets from the main model's labels [B, S]
    (labels[i] = token i + 1): position i predicts token i + 2 = labels[i +
    1], from the embedding of labels[i].  Ignored (-100) where either is
    missing: the last position always, and wherever labels are ignored —
    so labels that end in an ignored position mask the last two."""
    nxt = jnp.concatenate([labels[:, 1:],
                           jnp.full_like(labels[:, :1], -100)], axis=1)
    return jnp.where((labels >= 0) & (nxt >= 0), nxt, -100)


def train_loss(params, tokens, labels, config: HybridConfig,
               remat: bool = False):
    """The training loss of a pattern of `L`, `F`, `E` with one residual
    stream, over the tree `init_params` builds and the engine serves:
    CE(main) + mtp_loss_weight x CE(next-n module), both float32.

    Returns (loss, aux): aux["loss_main"], aux["loss_mtp"] (nought without
    the module), aux["moe"] = {counter: [expert layers] int32} over the main
    model's expert layers and then the module's (`TRAIN_COUNTERS`), and
    aux["load"] [expert layers, E_total]: the pairs each of the router's
    experts was chosen for (what `router_bias_step` reads)."""
    c = config
    head = gpt_mod.head_matrix(params, c)
    x = gpt_mod._embed(params, tokens, c)
    x, counters = _train_walk(c.layer_pattern, params["layers"], x, c, remat)
    with jax.named_scope("loss"):
        loss_main = _blocked_ce(x, params["lnf_w"], head, labels, c)
    loss_mtp = jnp.zeros((), jnp.float32)
    if c.num_nextn_predict_layers:
        m = params["mtp"]
        with jax.named_scope("mtp"):
            nxt = gpt_mod._embed(params, jnp.maximum(labels, 0), c)
            h = jnp.matmul(jnp.concatenate(
                [_norm(x, m["hnorm_w"], c), _norm(nxt, m["enorm_w"], c)],
                axis=-1), m["eh_proj"])
            h, more = _train_walk(MTP_PATTERN, m["layers"], h, c, remat)
            counters += more
            with jax.named_scope("loss"):
                loss_mtp = _blocked_ce(h, m["norm_w"], head,
                                       mtp_labels(labels), c)
    aux = {"loss_main": loss_main, "loss_mtp": loss_mtp}
    if counters:
        aux["moe"] = {k: jnp.stack([ctr[k] for ctr in counters])
                      for k in TRAIN_COUNTERS}
        aux["load"] = jnp.stack([ctr["load"] for ctr in counters])
    return loss_main + c.mtp_loss_weight * loss_mtp, aux


def router_bias_step(params, load, config: HybridConfig):
    """The router's bias rule (DeepSeek-V3's auxiliary-loss-free balancing):
    once a step, on the step's own counts, outside the gradient and outside
    the optimizer, every expert layer's

        router_bias_e += router_bias_update_rate x sign(mean load - load_e)

    `load` [expert layers, E_total] as `train_loss` returns it (the main
    model's expert layers in order, then the next-n module's).  Returns
    (the tree with the biases moved, how many entries moved)."""
    rate = config.router_bias_update_rate
    it = iter(load)
    moves = jnp.zeros((), jnp.int32)

    def moved(layers):
        nonlocal moves
        out = []
        for lp in layers:
            if "router_bias" in lp:
                n = next(it).astype(jnp.float32)
                step = jnp.sign(jnp.mean(n) - n)
                moves = moves + jnp.sum(step != 0, dtype=jnp.int32)
                lp = dict(lp, router_bias=lp["router_bias"] + rate * step)
            out.append(lp)
        return out

    with jax.named_scope("router_bias"):
        params = dict(params, layers=moved(params["layers"]))
        if "mtp" in params:
            params["mtp"] = dict(params["mtp"],
                                 layers=moved(params["mtp"]["layers"]))
    return params, moves
