"""GPT family — the flagship model (baseline ladder #4: GPT-3 1.3B hybrid parallel).

Two faces over one implementation:
- a pure-functional core (`init_params` / `forward` / `loss_fn`) over a stacked-block
  params pytree — the compiled hybrid-parallel trainer consumes this directly;
- a `GPTForCausalLM` nn.Layer wrapper exposing the eager paddle-style API.

TPU-native choices: block PARAMETERS are stacked on a leading L axis and scanned
over with `lax.scan` (one compiled block, XLA-friendly, and the L axis is what
pipeline parallelism shards); activations are the scan's carry.  So is the paged
KV pool in the serving passes (`_scan_paged_layers`): one donated buffer carried
through the layer loop and written and read in place, never scanned over — a
scanned pool is sliced and re-stacked whole in every layer.  Attention is the
Pallas flash kernel; norms hit the fused RMSNorm kernel; RoPE is fused into the
attention prologue.  Mirrors the reference's GPT in
PaddleNLP structure (embed -> L x [ln, attn, ln, mlp] -> ln -> tied lm head).
"""
from __future__ import annotations

import dataclasses
import math
from collections import OrderedDict
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..incubate.kernels.flash_attention import flash_attention_fused
from ..incubate.kernels.rms_norm import rms_norm_fused
from ..incubate.kernels.rope import apply_rope


@dataclasses.dataclass
class GPTConfig:
    """One transformer-family config covering GPT / LLaMA / BERT architectures.

    The reference implements these as separate model zoos (PaddleNLP gpt/llama/
    bert); TPU-first we keep ONE stacked-block functional core and express the
    family differences as config axes — every member then rides the same
    compiled hybrid-parallel trainer unchanged.
    """
    vocab_size: int = 50304
    hidden_size: int = 2048
    num_layers: int = 24
    num_heads: int = 16
    max_seq_len: int = 2048
    intermediate_size: Optional[int] = None
    use_rope: bool = True
    use_rms_norm: bool = False  # GPT-3 uses LayerNorm; llama preset flips this
    activation: str = "gelu"
    initializer_range: float = 0.02
    tie_word_embeddings: bool = True
    dtype: Any = jnp.float32
    # --- architecture axes beyond GPT ---
    num_kv_heads: Optional[int] = None  # GQA (llama-2/3): kv heads < q heads
    gated_ffn: bool = False     # SwiGLU: down(act(gate(x)) * up(x))
    use_bias: bool = True       # llama drops all linear biases
    causal: bool = True         # False = bidirectional encoder (BERT)
    norm_position: str = "pre"  # "post" = BERT-style residual-then-norm
    embed_norm: bool = False    # BERT: LayerNorm right after the embeddings
    final_norm: bool = True     # BERT (post-LN) has no final encoder norm
    type_vocab_size: int = 0    # BERT segment (token-type) embeddings
    mlm_head: bool = False      # BERT MLM transform (dense+act+LN) before head
    # MoE (ref incubate/distributed/models/moe): >0 replaces the dense FFN with
    # moe_num_experts capacity-routed experts in every block
    moe_num_experts: int = 0
    moe_topk: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # RMSNorm's epsilon (1e-6 is what every program computed before the field
    # existed) and the attention head width, hidden_size // num_heads unless
    # the architecture states another (heads x head_dim != hidden)
    rms_norm_eps: float = 1e-6
    head_dim: Optional[int] = None

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_heads

    @property
    def ffn_size(self):
        return self.intermediate_size or 4 * self.hidden_size

    @property
    def kv_heads(self):
        return self.num_kv_heads or self.num_heads

    @property
    def kv_layers(self):
        """Layers that keep keys and values in the paged pool (all of them,
        unless a layer pattern says otherwise: `models.hybrid`)."""
        return self.num_layers

    @property
    def qkv_dim(self):
        """Packed q|k|v output width: (heads + 2 * kv_heads) * head_dim."""
        return (self.num_heads + 2 * self.kv_heads) * self.head_dim


def gpt3_1p3b():
    """GPT-3 1.3B config (baseline ladder #4)."""
    return GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=24, num_heads=16,
                     max_seq_len=2048)


def gpt_tiny(seq_len=128):
    return GPTConfig(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                     max_seq_len=seq_len)


def gpt_moe_tiny(seq_len=128, num_experts=4, capacity_factor=2.0):
    return GPTConfig(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                     max_seq_len=seq_len, moe_num_experts=num_experts,
                     moe_capacity_factor=capacity_factor)


# ---------------------------------------------------------------------------
# functional core
# ---------------------------------------------------------------------------

def init_params(config: GPTConfig, key) -> Dict[str, Any]:
    c = config
    D, L, F, V = c.hidden_size, c.num_layers, c.ffn_size, c.vocab_size
    k = iter(jax.random.split(key, 24))
    std = c.initializer_range
    proj_std = std / math.sqrt(2 * L)  # GPT-2/3 residual-scaled init

    def norm_pair(shape):
        return jnp.ones(shape, c.dtype), jnp.zeros(shape, c.dtype)

    ln1_w, ln1_b = norm_pair((L, D))
    ln2_w, ln2_b = norm_pair((L, D))
    lnf_w, lnf_b = norm_pair((D,))
    blocks = {
        "ln1_w": ln1_w, "ln1_b": ln1_b,
        "qkv_w": (jax.random.normal(next(k), (L, D, c.qkv_dim)) * std).astype(c.dtype),
        "proj_w": (jax.random.normal(next(k), (L, c.num_heads * c.head_dim, D))
                   * proj_std).astype(c.dtype),
        "ln2_w": ln2_w, "ln2_b": ln2_b,
    }
    if c.use_bias:
        blocks["qkv_b"] = jnp.zeros((L, c.qkv_dim), c.dtype)
        blocks["proj_b"] = jnp.zeros((L, D), c.dtype)
    if c.moe_num_experts > 0:
        E = c.moe_num_experts
        blocks.update({
            "gate_w": (jax.random.normal(next(k), (L, D, E)) * std).astype(jnp.float32),
            "exp_fc1_w": (jax.random.normal(next(k), (L, E, D, F)) * std).astype(c.dtype),
            "exp_fc1_b": jnp.zeros((L, E, F), c.dtype),
            "exp_fc2_w": (jax.random.normal(next(k), (L, E, F, D)) * proj_std).astype(c.dtype),
            "exp_fc2_b": jnp.zeros((L, E, D), c.dtype),
        })
    else:
        blocks.update({
            "fc1_w": (jax.random.normal(next(k), (L, D, F)) * std).astype(c.dtype),
            "fc2_w": (jax.random.normal(next(k), (L, F, D)) * proj_std).astype(c.dtype),
        })
        if c.gated_ffn:
            blocks["fcg_w"] = (jax.random.normal(next(k), (L, D, F)) * std).astype(c.dtype)
        if c.use_bias:
            blocks["fc1_b"] = jnp.zeros((L, F), c.dtype)
            blocks["fc2_b"] = jnp.zeros((L, D), c.dtype)
            if c.gated_ffn:
                blocks["fcg_b"] = jnp.zeros((L, F), c.dtype)
    params = {
        "wte": (jax.random.normal(next(k), (V, D)) * std).astype(c.dtype),
        "blocks": blocks,
    }
    if c.final_norm or c.embed_norm:
        # post-LN encoders (BERT) reuse the lnf pair as the EMBEDDING norm
        params["lnf_w"], params["lnf_b"] = lnf_w, lnf_b
    if not c.use_rope:
        params["wpe"] = (jax.random.normal(next(k), (c.max_seq_len, D)) * std).astype(c.dtype)
    if c.type_vocab_size > 0:
        params["tte"] = (jax.random.normal(next(k), (c.type_vocab_size, D))
                         * std).astype(c.dtype)
    if c.mlm_head:
        params["mlm_w"] = (jax.random.normal(next(k), (D, D)) * std).astype(c.dtype)
        params["mlm_b"] = jnp.zeros((D,), c.dtype)
        params["mlm_ln_w"] = jnp.ones((D,), c.dtype)
        params["mlm_ln_b"] = jnp.zeros((D,), c.dtype)
    if not c.tie_word_embeddings:
        params["lm_head"] = (jax.random.normal(next(k), (D, V)) * std).astype(c.dtype)
    return params


def _norm(x, w, b, config):
    if config.use_rms_norm:
        return rms_norm_fused(x, w, config.rms_norm_eps)
    mu = jnp.mean(x.astype(jnp.float32), axis=-1, keepdims=True)
    var = jnp.var(x.astype(jnp.float32), axis=-1, keepdims=True)
    out = (x.astype(jnp.float32) - mu) * jax.lax.rsqrt(var + 1e-5)
    return (out * w + b).astype(x.dtype)


def _rope_tables(config, S, pos_offset=None):
    D = config.head_dim
    inv = 1.0 / (10000.0 ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    t = jnp.arange(S, dtype=jnp.float32)
    if pos_offset is not None:
        # context-parallel seq shard / decode position (traced or plain int)
        t = t + jnp.asarray(pos_offset, jnp.float32)
    freqs = jnp.outer(t, inv)
    return jnp.sin(freqs), jnp.cos(freqs)


def block_forward(bp, x, config: GPTConfig, mp_constraint=None, moe_impl=None,
                  attn_impl=None, pos_offset=None):
    """One transformer block; bp holds this block's (unstacked) weights.

    mp_constraint: optional callable applying sharding constraints on activations
    (set by the hybrid trainer to pin the tensor-parallel layout).
    moe_impl: optional callable (bp, x2d, config) -> (y2d, aux) overriding the
    MoE FFN (the hybrid trainer injects the ep-axis all-to-all version).
    attn_impl: optional callable (q, k, v) -> out overriding causal flash
    attention (the cp trainer injects ring attention).
    pos_offset: traced global position of x[:, 0] (context-parallel shards).

    Returns (out, aux) where aux is the MoE load-balance loss (0.0 when dense).
    """
    c = config
    B, S, D = x.shape
    H, KVH, hd = c.num_heads, c.kv_heads, c.head_dim
    pre = c.norm_position == "pre"

    h = _norm(x, bp["ln1_w"], bp["ln1_b"], c) if pre else x
    qkv = jnp.matmul(h, bp["qkv_w"])
    if "qkv_b" in bp:
        qkv = qkv + bp["qkv_b"]
    if mp_constraint:
        qkv = mp_constraint(qkv, "hidden_mp")
    q, kk, v = jnp.split(qkv, [H * hd, (H + KVH) * hd], axis=-1)
    q = q.reshape(B, S, H, hd)
    kk = kk.reshape(B, S, KVH, hd)
    v = v.reshape(B, S, KVH, hd)
    if c.use_rope:
        sin, cos = _rope_tables(c, S, pos_offset)
        q = apply_rope(q, sin, cos)
        kk = apply_rope(kk, sin, cos)
    if KVH != H:
        # GQA: each kv head serves H/KVH query heads (ref llama GQA repeat);
        # materializing the repeat keeps the flash kernel's H-uniform layout
        kk = jnp.repeat(kk, H // KVH, axis=2)
        v = jnp.repeat(v, H // KVH, axis=2)
    # saved under remat_policy_save_attention: the block replay then DCEs the qkv
    # matmul + rope (their only consumers' values are saved), keeping replay to
    # the proj/mlp chain
    q = checkpoint_name(q, "flash_qkv")
    kk = checkpoint_name(kk, "flash_qkv")
    v = checkpoint_name(v, "flash_qkv")
    if attn_impl is not None:
        attn = attn_impl(q, kk, v)
    else:
        attn = flash_attention_fused(q, kk, v, causal=c.causal)
    attn = attn.reshape(B, S, H * hd)
    attn = jnp.matmul(attn, bp["proj_w"])
    if "proj_b" in bp:
        attn = attn + bp["proj_b"]
    x = x + attn
    if not pre:
        x = _norm(x, bp["ln1_w"], bp["ln1_b"], c)

    h = _norm(x, bp["ln2_w"], bp["ln2_b"], c) if pre else x
    if c.moe_num_experts > 0:
        from ..incubate.distributed.models.moe.dispatch import moe_ffn_dense
        fn = moe_impl or moe_ffn_dense
        y, aux = fn(bp, h.reshape(B * S, D), c)
        x = x + y.reshape(B, S, D)
        if not pre:
            x = _norm(x, bp["ln2_w"], bp["ln2_b"], c)
        return x, aux
    up = jnp.matmul(h, bp["fc1_w"])
    if "fc1_b" in bp:
        up = up + bp["fc1_b"]
    act = jax.nn.gelu if c.activation == "gelu" else jax.nn.silu
    if c.gated_ffn:
        gate = jnp.matmul(h, bp["fcg_w"])
        if "fcg_b" in bp:
            gate = gate + bp["fcg_b"]
        if mp_constraint:
            up = mp_constraint(up, "ffn_mp")
            gate = mp_constraint(gate, "ffn_mp")
        h = act(gate) * up
    else:
        if mp_constraint:
            up = mp_constraint(up, "ffn_mp")
        h = act(up)
    h = jnp.matmul(h, bp["fc2_w"])
    if "fc2_b" in bp:
        h = h + bp["fc2_b"]
    x = x + h
    if not pre:
        x = _norm(x, bp["ln2_w"], bp["ln2_b"], c)
    return x, jnp.zeros((), jnp.float32)


def run_blocks(blocks, x, config, mp_constraint=None, remat=False, moe_impl=None,
               attn_impl=None, pos_offset=None):
    """Scan the stacked blocks: one compiled block body, L iterations.

    Returns (out, aux) — aux is the summed MoE load-balance loss over blocks."""
    from ..incubate.kernels.flash_attention import remat_policy_save_attention

    body = block_forward
    if remat:
        # config AND mp_constraint are static so sharding constraints survive
        # remat.  The policy saves the flash-attention out/lse residuals, so the
        # block replay re-runs only the (cheap) matmul chain — attention forward
        # runs exactly once per step instead of ~3x (round-1 remat tax).
        body = jax.checkpoint(block_forward, static_argnums=(2, 3, 4, 5),
                              policy=remat_policy_save_attention())

    def step(carry, bp):
        x, aux = carry
        out, a = body(bp, x, config, mp_constraint, moe_impl, attn_impl,
                      pos_offset)
        return (out, aux + a), None

    # inside a shard_map (pp loop) x is varying over the manual axes; the aux
    # carry must carry the same vma type or scan rejects the carry signature
    aux0 = jnp.zeros((), jnp.float32)
    vma = jax.typeof(x).vma
    if vma:
        aux0 = jax.lax.pcast(aux0, tuple(vma), to="varying")
    (out, aux), _ = jax.lax.scan(step, (x, aux0), blocks)
    return out, aux


def embed_prologue(params, x, config: GPTConfig, type_ids=None):
    """Everything between the token-table lookup and the first block:
    learned positions, segment (token-type) embeddings, embedding norm.
    type_ids default to segment 0 (single-sentence BERT batches)."""
    S = x.shape[1]
    if not config.use_rope:
        x = x + params["wpe"][:S]
    if config.type_vocab_size > 0:
        if type_ids is None:
            x = x + params["tte"][0]
        else:
            x = x + jnp.take(params["tte"], type_ids, axis=0)
    if config.embed_norm:
        x = _norm(x, params["lnf_w"], params["lnf_b"], config)
    return x


def epilogue(params, h, config: GPTConfig):
    """Final norm (pre-LN stacks) and/or the BERT MLM transform
    (dense + act + LN, ref BertPretrainingHeads) before the vocab head."""
    if config.final_norm:
        h = _norm(h, params["lnf_w"], params["lnf_b"], config)
    if config.mlm_head:
        h = jnp.matmul(h, params["mlm_w"]) + params["mlm_b"]
        h = jax.nn.gelu(h) if config.activation == "gelu" else jax.nn.silu(h)
        h = _norm(h, params["mlm_ln_w"], params["mlm_ln_b"], config)
    return h


def _deq(q, scale, dtype):
    """Traced twin of `quantization.serving.dequantize_weight`: int8 values
    times float32 per-channel scale, cast into the compute dtype.  EVERY
    in-program weight dequant (blocks, embedding rows, head) goes through
    this one expression so the scheme cannot desynchronize between sites."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def _w(bp, name, dtype):
    """Weight `name` from a (possibly weight-quantized) param subtree.

    `quantization.serving.quantize_serving_params` replaces a serving matmul
    weight with the pair `name_q` (int8) + `name_scale` (float32, per output
    channel); this helper dequantizes it on the fly into the compute dtype.
    Called inside the layer scan, so the fp copy of a quantized weight only
    ever exists one block at a time — at-rest HBM stays int8."""
    q = bp.get(name + "_q")
    if q is None:
        return bp[name]
    return _deq(q, bp[name + "_scale"], dtype)


def _mesh_mp(mesh) -> int:
    """Tensor-parallel degree of a serving mesh (1 when mesh is None or has
    no "mp" axis) — the one switch the mp-aware serving fns key off."""
    if mesh is None:
        return 1
    return int(mesh.shape.get("mp", 1))


def _embed(params, tokens, config: GPTConfig, mesh=None):
    """Token-table lookup, weight-quantization aware: int8 `wte_q` rows are
    gathered first and dequantized by their per-row scale — the fp table is
    never materialized.

    Under an mp serving mesh the table is VOCAB-SHARDED (`wte` rows split
    over "mp" by `parallel.hybrid.serving_param_specs`), and the lookup runs
    as the Megatron vocab-parallel form — masked LOCAL take + psum inside a
    manual region, mirroring the trainer's `_vp_embed` — because a
    vocab-sharded gather under auto axes CHECK-crashes XLA's SPMD
    partitioner.  Exactly one shard owns each token id, so the psum of
    masked rows is bit-exact vs the replicated take."""
    mp = _mesh_mp(mesh)
    if mp <= 1:
        if "wte_q" in params:
            rows = jnp.take(params["wte_q"], tokens, axis=0)
            scale = jnp.take(params["wte_scale"], tokens, axis=0)
            return _deq(rows, scale, config.dtype)
        return jnp.take(params["wte"], tokens, axis=0)

    from jax.sharding import PartitionSpec as P
    quant = "wte_q" in params

    def local(table, scale, tok):
        r = jax.lax.axis_index("mp")
        Vl = table.shape[0]
        ids = tok - r * Vl
        ok = (ids >= 0) & (ids < Vl)
        safe = jnp.clip(ids, 0, Vl - 1)
        rows = jnp.take(table, safe, axis=0)
        if quant:
            rows = _deq(rows, jnp.take(scale, safe, axis=0), config.dtype)
        rows = jnp.where(ok[..., None], rows, jnp.zeros((), rows.dtype))
        return jax.lax.psum(rows, "mp")

    sm = jax.shard_map(
        local, mesh=mesh, axis_names={"mp"},
        in_specs=(P("mp", None), P("mp", None), P()), out_specs=P())
    if quant:
        return sm(params["wte_q"], params["wte_scale"], tokens)
    # fp path: feed the scale slot a zero-width view so one signature serves
    # both dtypes (the branch is static, the dummy is dead code when traced).
    return sm(params["wte"], params["wte"][:, :0], tokens)


def head_matrix(params, config: GPTConfig):
    if config.tie_word_embeddings:
        if "wte_q" in params:
            return _deq(params["wte_q"], params["wte_scale"],
                        config.dtype).T
        return params["wte"].T
    if "lm_head_q" in params:
        return _deq(params["lm_head_q"], params["lm_head_scale"],
                    config.dtype)
    return params["lm_head"]


def head_logits(x, params, config: GPTConfig, mesh=None):
    """Vocab projection `x @ head` for the serving executables.

    Quantization-aware WITHOUT materializing the fp [V, D] table inside the
    step (at real vocab sizes that transient alone would blow the declared
    peak-HBM budgets): the matmul runs against the int8 table upcast to the
    compute dtype — int8 values are exact in bf16/f32 — and the per-vocab
    scales multiply the LOGITS columns afterward, which is the same math
    because the scale is constant along the contraction dim.  The transient
    is logits-shaped, not weight-shaped.

    Under an mp mesh the head weight arrives VOCAB-SHARDED over "mp"
    (`serving_param_specs`), the matmul partitions as a plain local GEMM
    against the shard (matmuls — unlike gathers — partition fine under auto
    GSPMD), and the constraint pins the logits' vocab axis sharded so each
    chip holds [.., V/mp] and the replicated [.., V] buffer NEVER
    materializes; the downstream pick merges per-shard (value, index) pairs
    (`sharded_argmax` / `sample_token`)."""
    if config.tie_word_embeddings and "wte_q" in params:
        scale = params["wte_scale"].T                       # [V, 1] -> [1, V]
        logits = (jnp.matmul(x, params["wte_q"].T.astype(config.dtype))
                  * scale).astype(config.dtype)
    elif not config.tie_word_embeddings and "lm_head_q" in params:
        scale = params["lm_head_scale"]                     # already [1, V]
        logits = (jnp.matmul(x, params["lm_head_q"].astype(config.dtype))
                  * scale).astype(config.dtype)
    else:
        logits = jnp.matmul(x, head_matrix(params, config))
    if _mesh_mp(mesh) > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = P(*([None] * (logits.ndim - 1)), "mp")
        logits = jax.lax.with_sharding_constraint(
            logits, NamedSharding(mesh, spec))
    return logits


def sharded_argmax(logits, mesh=None):
    """First-occurrence argmax over the vocab (last) axis, mp-aware.

    mesh None / mp=1 is plain `jnp.argmax`.  Under an mp mesh the logits
    arrive vocab-sharded and each chip reduces its local shard to a
    (value, global index) pair; a pmax merges the value and the tie-break
    takes the LOWEST global index among the shards holding the max (pmin
    over index-where-max, V as the sentinel) — exactly `jnp.argmax`'s
    first-occurrence rule, so mp∈{1,2,4} emit byte-identical tokens.  The
    merge runs in a manual region and moves one scalar pair per row over
    the mesh — the replicated [.., V] logits buffer never exists."""
    if _mesh_mp(mesh) <= 1:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    from jax.sharding import PartitionSpec as P
    V = logits.shape[-1]
    lead = logits.ndim - 1

    def local(lg):
        r = jax.lax.axis_index("mp")
        Vl = lg.shape[-1]
        lv = jnp.max(lg, axis=-1)
        li = jnp.argmax(lg, axis=-1).astype(jnp.int32) + r * Vl
        gm = jax.lax.pmax(lv, "mp")
        cand = jnp.where(lv == gm, li, V)
        return jax.lax.pmin(cand, "mp").astype(jnp.int32)

    return jax.shard_map(
        local, mesh=mesh, axis_names={"mp"},
        in_specs=(P(*([None] * lead), "mp"),), out_specs=P())(logits)


def backbone(params, tokens, config: GPTConfig, mp_constraint=None, remat=False,
             moe_impl=None, type_ids=None, attn_impl=None):
    """Shared trunk: tokens [B, S] -> (activations [B, S, D], head, moe aux)."""
    x = jnp.take(params["wte"], tokens, axis=0)
    x = embed_prologue(params, x, config, type_ids)
    if mp_constraint:
        x = mp_constraint(x, "act")
    x, aux = run_blocks(params["blocks"], x, config, mp_constraint, remat=remat,
                        moe_impl=moe_impl, attn_impl=attn_impl)
    x = epilogue(params, x, config)
    return x, head_matrix(params, config), aux


def forward(params, tokens, config: GPTConfig, mp_constraint=None, remat=False):
    """tokens [B, S] int32 -> logits [B, S, V]."""
    x, head, _ = backbone(params, tokens, config, mp_constraint, remat)
    return jnp.matmul(x, head)


def _ce_sums(logits, labels):
    """(-sum log p[label], count) over valid labels (-100 = ignore)."""
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    safe = jnp.where(labels < 0, 0, labels)
    picked = jnp.take_along_axis(lp, safe[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return -jnp.sum(picked * mask), jnp.sum(mask)


def loss_fn(params, tokens, labels, config: GPTConfig, mp_constraint=None,
            remat=False, loss_chunk: Optional[int] = 512, moe_impl=None,
            attn_impl=None):
    """Causal LM loss; labels [B, S] with -100 = ignore.

    loss_chunk: when set, the LM head + softmax run over sequence chunks inside a
    rematerialized scan, so the [B, S, V] float32 log-probs never materialize —
    the dominant HBM transient at GPT-3 vocab (V=50k: 3.3 GB at B=8, S=2048).
    """
    x, head, aux = backbone(params, tokens, config, mp_constraint, remat, moe_impl,
                            attn_impl=attn_impl)
    moe_pen = config.moe_aux_weight * aux if config.moe_num_experts > 0 else 0.0
    B, S, D = x.shape
    if not loss_chunk or S % loss_chunk != 0 or S <= loss_chunk:
        loss_sum, n = _ce_sums(jnp.matmul(x, head), labels)
        return loss_sum / jnp.maximum(n, 1.0) + moe_pen

    nc = S // loss_chunk
    xc = jnp.swapaxes(x.reshape(B, nc, loss_chunk, D), 0, 1)       # [nc,B,c,D]
    labc = jnp.swapaxes(labels.reshape(B, nc, loss_chunk), 0, 1)

    def body(carry, xl):
        xx, ll = xl
        ls, n = _ce_sums(jnp.matmul(xx, head), ll)
        return (carry[0] + ls, carry[1] + n), None

    # remat the chunk: backward replays the chunk's head matmul instead of saving
    # per-chunk log-probs (head flops are ~5% of the model; the 3 GB is not)
    (loss_sum, n), _ = jax.lax.scan(jax.checkpoint(body), (0.0, 0.0), (xc, labc))
    return loss_sum / jnp.maximum(n, 1.0) + moe_pen


def count_params(params):
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# Layer wrapper (eager paddle-style API over the same functional core)
# ---------------------------------------------------------------------------

from ..core.tensor import Tensor, apply  # noqa: E402
from ..nn.layer.layers import Layer  # noqa: E402


class GPTForCausalLM(Layer):
    def __init__(self, config: GPTConfig = None, **kwargs):
        super().__init__()
        self.config = config or GPTConfig(**kwargs)
        from ..core import generator as _gen
        raw = init_params(self.config, _gen.next_key())
        from ..core.tensor import Parameter
        self._param_tree = jax.tree_util.tree_map(Parameter, raw)
        # register leaves so Layer machinery (state_dict, optimizers) sees them
        flat, self._treedef = jax.tree_util.tree_flatten(self._param_tree)
        for i, p in enumerate(flat):
            self.add_parameter(f"p{i}", p)
        self._flat_params = flat

    def forward(self, input_ids, labels=None):
        # run via apply so the tape records one whole-model node
        datas = [p for p in self._flat_params]
        tokens = input_ids._data if isinstance(input_ids, Tensor) else jnp.asarray(input_ids)
        cfg = self.config
        if labels is not None:
            lab = labels._data if isinstance(labels, Tensor) else jnp.asarray(labels)

            def g(*leafs):
                tree = jax.tree_util.tree_unflatten(self._treedef, list(leafs))
                return loss_fn(tree, tokens, lab, cfg)
            return apply("gpt_loss", g, *datas)

        def h(*leafs):
            tree = jax.tree_util.tree_unflatten(self._treedef, list(leafs))
            return forward(tree, tokens, cfg)
        return apply("gpt_forward", h, *datas)

    def generate(self, input_ids, max_new_tokens=32, temperature=0.0,
                 top_k=None, eos_token_id=None):
        """KV-cache autoregressive decoding (see module-level `generate`)."""
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        out = generate(self.params_pytree(), ids, self.config,
                       max_new_tokens=max_new_tokens, temperature=temperature,
                       top_k=top_k, eos_token_id=eos_token_id)
        return Tensor(out)

    def params_pytree(self):
        """Raw jnp pytree view (shared buffers) for the compiled trainer."""
        return jax.tree_util.tree_unflatten(
            self._treedef, [p._data for p in self._flat_params])

    def load_pytree(self, tree):
        flat, _ = jax.tree_util.tree_flatten(tree)
        for p, d in zip(self._flat_params, flat):
            p._data = d


def llama_tiny(seq_len=128):
    """Llama-architecture preset: RMSNorm + SwiGLU + GQA + no biases +
    untied head — the full architecture family, scaled tiny."""
    return GPTConfig(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                     num_kv_heads=2, max_seq_len=seq_len, use_rms_norm=True,
                     activation="silu", gated_ffn=True, use_bias=False,
                     tie_word_embeddings=False, intermediate_size=172)


def llama2_7b():
    """Llama-2 7B shape family (ref PaddleNLP llama configs)."""
    return GPTConfig(vocab_size=32000, hidden_size=4096, num_layers=32,
                     num_heads=32, max_seq_len=4096, use_rms_norm=True,
                     activation="silu", gated_ffn=True, use_bias=False,
                     tie_word_embeddings=False, intermediate_size=11008)


def llama3_8b():
    """Llama-3 8B shape family: GQA with 8 kv heads."""
    return GPTConfig(vocab_size=128256, hidden_size=4096, num_layers=32,
                     num_heads=32, num_kv_heads=8, max_seq_len=8192,
                     use_rms_norm=True, activation="silu", gated_ffn=True,
                     use_bias=False, tie_word_embeddings=False,
                     intermediate_size=14336)


def bert_config(vocab_size=30522, hidden_size=768, num_layers=12, num_heads=12,
                max_seq_len=512, type_vocab_size=2, intermediate_size=None):
    """BERT-architecture config (ref PaddleNLP bert): bidirectional post-LN
    encoder, learned positions, segment embeddings, embedding LayerNorm, MLM
    transform head tied to the embeddings.  NSP is intentionally dropped
    (modern MLM-only pretraining; RoBERTa recipe)."""
    return GPTConfig(vocab_size=vocab_size, hidden_size=hidden_size,
                     num_layers=num_layers, num_heads=num_heads,
                     max_seq_len=max_seq_len, use_rope=False, causal=False,
                     norm_position="post", embed_norm=True, final_norm=False,
                     type_vocab_size=type_vocab_size, mlm_head=True,
                     intermediate_size=intermediate_size)


def bert_base():
    """BERT-base (baseline ladder #3)."""
    return bert_config()


def bert_tiny(seq_len=128):
    return bert_config(vocab_size=256, hidden_size=64, num_layers=2,
                       num_heads=4, max_seq_len=seq_len)


# ---------------------------------------------------------------------------
# KV-cache autoregressive decoding (ref PaddleNLP generation + fused
# variable-length attention; TPU-native: static-shape cache + lax.scan decode)
# ---------------------------------------------------------------------------

def init_cache(config: GPTConfig, batch: int, max_len: int):
    """Per-layer KV cache [L, B, max_len, KVH, hd] (static shapes for jit).
    GQA caches only the kv heads — the cache shrinks by H/KVH (the point of
    GQA for serving)."""
    c = config
    shape = (c.num_layers, batch, max_len, c.kv_heads, c.head_dim)
    return {"k": jnp.zeros(shape, c.dtype), "v": jnp.zeros(shape, c.dtype)}


def _ffn_dense(bp, h, c: GPTConfig, mp_constraint=None):
    """Dense-FFN body shared by the decode/prefill paths (gated + bias aware,
    int8-weight aware via `_w`).  mp_constraint (serving tensor parallel)
    pins the column-sharded hidden."""
    up = jnp.matmul(h, _w(bp, "fc1_w", c.dtype))
    if "fc1_b" in bp:
        up = up + bp["fc1_b"]
    act = jax.nn.gelu if c.activation == "gelu" else jax.nn.silu
    if c.gated_ffn:
        gate = jnp.matmul(h, _w(bp, "fcg_w", c.dtype))
        if "fcg_b" in bp:
            gate = gate + bp["fcg_b"]
        if mp_constraint:
            up = mp_constraint(up, "ffn_mp")
            gate = mp_constraint(gate, "ffn_mp")
        h = act(gate) * up
    else:
        if mp_constraint:
            up = mp_constraint(up, "ffn_mp")
        h = act(up)
    out = jnp.matmul(h, _w(bp, "fc2_w", c.dtype))
    if "fc2_b" in bp:
        out = out + bp["fc2_b"]
    return out


def _unpack_qkv(qkv, c: GPTConfig, parts: int = 1):
    """Split a packed qkv matmul output into flat q/k/v column groups,
    partition-aware.

    parts=1 is the trainer's global `[q | k | v]` layout.  parts=mp reads
    the PER-PARTITION layout `[q_0 k_0 v_0 | q_1 k_1 v_1 | ...]` the engine
    places under mp (`parallel.hybrid.pack_qkv_partitions`), whose `parts`
    contiguous column groups are exactly each chip's head slices — so the
    placed qkv shard is consumed where it lands, with no replicate→reslice
    staging.  Concatenating the per-partition q (then k, then v) segments
    restores GLOBAL head order, so for matching permutations the result is
    bit-identical to the parts=1 unpack of the unpermuted weight; every
    reshape/slice here moves along locally-owned axes (the packed column
    axis shards evenly over `parts`), so under GSPMD the unpack is free."""
    H, KVH, hd = c.num_heads, c.kv_heads, c.head_dim
    if parts <= 1:
        return jnp.split(qkv, [H * hd, (H + KVH) * hd], axis=-1)
    lead = qkv.shape[:-1]
    Hl, KVHl = H // parts, KVH // parts
    g = qkv.reshape(*lead, parts, (Hl + 2 * KVHl) * hd)
    q = g[..., :Hl * hd].reshape(*lead, H * hd)
    k = g[..., Hl * hd:(Hl + KVHl) * hd].reshape(*lead, KVH * hd)
    v = g[..., (Hl + KVHl) * hd:].reshape(*lead, KVH * hd)
    return q, k, v


def _decode_qkv(bp, x, c: GPTConfig, pos, parts: int = 1):
    """Pre-norm + packed qkv + rope for a single-token decode input.

    x [B, D]; pos is a scalar (dense contiguous cache) or a [B] vector
    (per-slot positions, the paged engine's slot-indexed decode).
    Returns post-rope q [B, H, hd], k, v [B, KVH, hd].  `parts` selects the
    packed-qkv column layout (`_unpack_qkv`)."""
    B = x.shape[0]
    H, KVH, hd = c.num_heads, c.kv_heads, c.head_dim
    h = _norm(x, bp["ln1_w"], bp["ln1_b"], c) if c.norm_position == "pre" \
        else x
    qkv = jnp.matmul(h, _w(bp, "qkv_w", c.dtype))
    if "qkv_b" in bp:
        qkv = qkv + bp["qkv_b"]
    q, k, v = _unpack_qkv(qkv, c, parts)
    q = q.reshape(B, H, hd)
    k = k.reshape(B, KVH, hd)
    v = v.reshape(B, KVH, hd)
    if c.use_rope:
        sin, cos = _rope_tables(c, 1, pos_offset=pos)
        if jnp.ndim(pos) > 0:
            # per-slot positions: tables are [B, half] -> feed apply_rope's
            # batched [B, S=1, half] branch
            sin, cos = sin[:, None], cos[:, None]
        q = apply_rope(q[:, None], sin, cos)[:, 0]
        k = apply_rope(k[:, None], sin, cos)[:, 0]
    return q, k, v


def _rope_tables_at(config, pos):
    """Rope sin/cos at explicit (possibly traced, per-batch) positions.
    pos [B, T] int32 -> tables [B, T, head_dim/2] for apply_rope's batched
    branch — the chunked-prefill path, whose chunk starts at q_offset != 0."""
    D = config.head_dim
    inv = 1.0 / (10000.0 ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    freqs = pos.astype(jnp.float32)[..., None] * inv
    return jnp.sin(freqs), jnp.cos(freqs)


def yarn_inv_freq(dim: int, theta: float, scaling: Optional[dict]):
    """Rotary inverse frequencies [dim / 2] float32: plain (`scaling` None)
    or YaRN's (Peng et al., arXiv:2309.00071, as the DeepSeek-V2/V3 code
    computes them): a frequency whose wavelength fits the original context
    `beta_fast` times or more is kept, one that fits it `beta_slow` times or
    fewer is divided by `factor`, and a linear ramp over the index blends the
    two in between."""
    idx = jnp.arange(0, dim, 2, dtype=jnp.float32)
    extra = 1.0 / (theta ** (idx / dim))
    if scaling is None:
        return extra

    def correction_dim(rotations):
        return dim * math.log(scaling["original_max_position_embeddings"] /
                              (rotations * 2 * math.pi)) / \
            (2 * math.log(theta))
    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) /
                    (high - low), 0.0, 1.0)
    return extra / scaling["factor"] * ramp + extra * (1.0 - ramp)


def yarn_softmax_scale(score_dim: int, scaling: Optional[dict]) -> float:
    """score_dim^-1/2, times mscale^2 where YaRN's `mscale_all_dim` is set:
    mscale = 0.1 * mscale_all_dim * ln(factor) + 1."""
    scale = 1.0 / math.sqrt(score_dim)
    if scaling and scaling.get("mscale_all_dim") and scaling["factor"] > 1:
        m = 0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) + 1
        scale *= m * m
    return scale


def yarn_rope_tables_at(dim: int, theta: float, scaling: Optional[dict], pos):
    """`_rope_tables_at` for a rotary part of `dim` columns with
    `yarn_inv_freq`'s frequencies: pos [B, T] -> sin, cos [B, T, dim / 2].
    (The tables carry no magnitude correction: with `mscale` equal to
    `mscale_all_dim`, as published, it is 1, and `yarn_softmax_scale`
    carries mscale^2.)"""
    freqs = pos.astype(jnp.float32)[..., None] * \
        yarn_inv_freq(dim, theta, scaling)
    return jnp.sin(freqs), jnp.cos(freqs)


def _prefill_qkv(bp, x, c: GPTConfig, pos=None, parts: int = 1):
    """Pre-norm + packed qkv + rope over a [B, T, D] prompt (positions
    0..T-1, or explicit per-batch positions `pos` [B, T] for chunked
    prefill).  Returns post-rope q [B, T, H, hd], k, v [B, T, KVH, hd].
    `parts` selects the packed-qkv column layout (`_unpack_qkv`)."""
    B, T, _ = x.shape
    H, KVH, hd = c.num_heads, c.kv_heads, c.head_dim
    h = _norm(x, bp["ln1_w"], bp["ln1_b"], c) if c.norm_position == "pre" \
        else x
    qkv = jnp.matmul(h, _w(bp, "qkv_w", c.dtype))
    if "qkv_b" in bp:
        qkv = qkv + bp["qkv_b"]
    q, k, v = _unpack_qkv(qkv, c, parts)
    q = q.reshape(B, T, H, hd)
    k = k.reshape(B, T, KVH, hd)
    v = v.reshape(B, T, KVH, hd)
    if c.use_rope:
        sin, cos = _rope_tables(c, T) if pos is None else _rope_tables_at(c, pos)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
    return q, k, v


def _layer_tail(bp, x, attn, c: GPTConfig, mp_constraint=None):
    """Shared post-attention half of a decode/prefill block: out-proj +
    residual (+ post-LN) + FFN/MoE + residual (+ post-LN).  attn is the
    head-flattened [..., D] attention output, x the block input (same rank)."""
    if mp_constraint:
        # head-sharded attention flattens to a column-sharded hidden; pinning
        # it keeps the row-parallel proj matmul a local-contraction + psum
        attn = mp_constraint(attn, "hidden_mp")
    attn = jnp.matmul(attn, _w(bp, "proj_w", c.dtype))
    if "proj_b" in bp:
        attn = attn + bp["proj_b"]
    x = x + attn
    if c.norm_position != "pre":
        x = _norm(x, bp["ln1_w"], bp["ln1_b"], c)
    h = _norm(x, bp["ln2_w"], bp["ln2_b"], c) if c.norm_position == "pre" \
        else x
    if c.moe_num_experts > 0:
        from ..incubate.distributed.models.moe.dispatch import moe_ffn_dense
        lead = h.shape[:-1]
        y, _ = moe_ffn_dense(bp, h.reshape(-1, c.hidden_size), c)
        y = y.reshape(*lead, c.hidden_size)
    else:
        y = _ffn_dense(bp, h, c, mp_constraint)
    x = x + y
    if c.norm_position != "pre":
        x = _norm(x, bp["ln2_w"], bp["ln2_b"], c)
    return x


def decode_step(params, token, cache, pos, config: GPTConfig):
    """One autoregressive step: token [B] int32 at position `pos` (traced).

    Returns (logits [B, V], updated cache).  Attention is a dense dot against
    the cache with a position mask — at decode T=1 the MXU matmul IS the
    fused path; no flash kernel needed.
    """
    c = config
    assert c.causal, "KV-cache decoding requires a causal model"
    B = token.shape[0]
    D, H, KVH, hd = c.hidden_size, c.num_heads, c.kv_heads, c.head_dim
    G = H // KVH                                             # queries per kv head
    x = jnp.take(params["wte"], token, axis=0)               # [B, D]
    if not c.use_rope:
        x = x + jax.lax.dynamic_index_in_dim(params["wpe"], pos, keepdims=False)

    max_len = cache["k"].shape[2]
    kv_pos = jnp.arange(max_len)

    def layer(x, layer_in):
        bp, kc, vc = layer_in                               # caches [B,S,KVH,hd]
        q, k, v = _decode_qkv(bp, x, c, pos)
        kc = jax.lax.dynamic_update_slice_in_dim(kc, k[:, None], pos, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, v[:, None], pos, axis=1)
        # grouped attention against the KVH-head cache: q [B, KVH, G, hd]
        qg = q.reshape(B, KVH, G, hd)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, kc,
                       preferred_element_type=jnp.float32) / math.sqrt(hd)
        s = jnp.where((kv_pos <= pos)[None, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        attn = jnp.einsum("bkgs,bskd->bkgd", p.astype(vc.dtype), vc)
        x = _layer_tail(bp, x, attn.reshape(B, H * hd), c)
        return x, (kc, vc)

    def scan_body(carry, inp):
        out, kv = layer(carry, inp)
        return out, kv

    x, (new_k, new_v) = jax.lax.scan(
        scan_body, x, (params["blocks"], cache["k"], cache["v"]))
    x = epilogue(params, x, c)
    return head_logits(x, params, c), {"k": new_k, "v": new_v}


def prefill(params, input_ids, config: GPTConfig, cache):
    """One batched forward over the prompt that also fills the KV cache.

    Returns (last-position logits [B, V], cache with positions [0, Tp) set).
    The prompt runs as ONE dense pass (MXU-sized matmuls + causal attention),
    not Tp serial decode steps.
    """
    c = config
    assert c.causal, "KV-cache decoding requires a causal model"
    B, Tp = input_ids.shape
    D, H, KVH, hd = c.hidden_size, c.num_heads, c.kv_heads, c.head_dim
    x = jnp.take(params["wte"], input_ids, axis=0)
    if not c.use_rope:
        x = x + params["wpe"][:Tp]

    def layer(x, layer_in):
        bp, kc, vc = layer_in
        q, k, v = _prefill_qkv(bp, x, c)
        kc = jax.lax.dynamic_update_slice_in_dim(kc, k, 0, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, v, 0, axis=1)
        if KVH != H:
            k = jnp.repeat(k, H // KVH, axis=2)
            v = jnp.repeat(v, H // KVH, axis=2)
        attn = flash_attention_fused(q, k, v, causal=True).reshape(
            B, Tp, H * hd)
        x = _layer_tail(bp, x, attn, c)
        return x, (kc, vc)

    x, (new_k, new_v) = jax.lax.scan(
        lambda carry, inp: layer(carry, inp),
        x, (params["blocks"], cache["k"], cache["v"]))
    x = epilogue(params, x[:, -1], c)
    return head_logits(x, params, c), {"k": new_k, "v": new_v}


# ---------------------------------------------------------------------------
# Paged KV cache (ref vLLM PagedAttention, SOSP 2023): KV lives in a static
# pool of fixed-size pages + per-slot page tables, so serving memory scales
# with live tokens instead of B x max_seq_len.  `inference.engine.LLMEngine`
# owns the page accounting; these are the compiled model-side steps.
# ---------------------------------------------------------------------------

def init_paged_cache(config: GPTConfig, num_pages: int, page_size: int,
                     kv_dtype=None):
    """Per-layer paged KV pool [L, num_pages, page_size, KVH, hd].
    Page 0 is reserved as the null page: inactive slots and padded bucket
    tails write there, and it is never read (masked by per-slot length).

    kv_dtype="int8" stores int8 k/v plus per-token-per-head float32 scale
    lanes `k_scale`/`v_scale` [L, num_pages, page_size, KVH]: every KV write
    quantizes in-program (`_quantize_kv`) and the paged-attention kernels
    dequantize per page on read.  Per-token scales keep the token-granular
    write paths (decode append, chunked prefill, verify rollback, COW, swap)
    exact and write-order independent — a coarser per-page scale would need
    a lossy rescale of already-written tokens.  The default (None) is the
    byte-identical fp pool."""
    from ..quantization.serving import KV_SCALE_DTYPE, normalize_quant_dtype
    c = config
    shape = (c.num_layers, num_pages, page_size, c.kv_heads, c.head_dim)
    if normalize_quant_dtype(kv_dtype, "kv_dtype") == "int8":
        sshape = shape[:-1]
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(sshape, KV_SCALE_DTYPE),
                "v_scale": jnp.zeros(sshape, KV_SCALE_DTYPE)}
    return {"k": jnp.zeros(shape, c.dtype), "v": jnp.zeros(shape, c.dtype)}


def _quantize_kv(x):
    """Symmetric per-token-per-head int8 quantization of a KV write
    `[..., hd]` -> (int8 values [..., hd], float32 scale [...]).  Runs
    INSIDE the serving executables at every KV write; the matching dequant
    is `value * scale` in the paged-attention kernels/oracles."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(absmax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127.0, 127.0) \
        .astype(jnp.int8)
    return q, scale


def _kv_scales(kv):
    """The attention entries' kv_scales lane: (k_scale, v_scale) of a
    quantized pool, None for the fp pool."""
    if "k_scale" in kv:
        return kv["k_scale"], kv["v_scale"]
    return None


def serving_mp_constraint(mesh):
    """Sharding-constraint callable for the tensor-parallel serving path
    (multi-chip `LLMEngine`): pins activations so GSPMD partitions the paged
    executables Megatron-style instead of guessing.  Kinds: "heads" shards the
    second-to-last ([..., H|KVH, hd]) axis over mp (attention is per-head
    independent); "ffn_mp"/"hidden_mp" column-shard the last axis.  Returns
    None when mesh has no mp axis > 1, so call sites read
    `if pin: x = pin(x, kind)` — zero-cost single chip."""
    if mesh is None or int(dict(mesh.shape).get("mp", 1)) <= 1:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P

    def pin(x, kind):
        if kind == "heads":
            spec = P(*([None] * (x.ndim - 2)), "mp", None)
        else:   # "hidden_mp" / "ffn_mp"
            spec = P(*([None] * (x.ndim - 1)), "mp")
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return pin


def _scan_paged_layers(params, x, cache, layer):
    """The layer loop of every paged pass.  The pool is a loop CARRY that each
    layer updates in place — never a scanned input and a stacked output, which
    would slice every layer's [P, page, KVH, hd] plane out of the pool and
    write it back whole (the entire pool read and written ~3 times a program
    to store a few hundred KB of new keys and values).

    Inside the loop every lane of `cache` ([L, P, page, ...]) is viewed as
    [L*P, page, ...] (a reshape of leading axes: no data moves), so layer l
    owns rows [l*P, (l+1)*P).  `layer(bp, x, pool, base)` writes its KV at page
    ids `base + page_id` and attends through `page_table + base` (base =
    l*P; the null page of layer l is row base + 0 — route padded rows to
    page 0 BEFORE adding base).  The attention entries read the flat pool
    unchanged: they find a page by its id in the leading axis and skip by
    lengths, not by page id.  Returns (x, cache in its [L, P, ...] layout)."""
    L, P = cache["k"].shape[:2]
    pool = {n: a.reshape((L * P,) + a.shape[2:]) for n, a in cache.items()}

    def body(carry, layer_in):
        bp, l = layer_in
        return layer(bp, *carry, l * P), None

    (x, pool), _ = jax.lax.scan(
        body, (x, pool), (params["blocks"], jnp.arange(L, dtype=jnp.int32)))
    return x, {n: a.reshape(cache[n].shape) for n, a in pool.items()}


def prefill_paged(params, input_ids, config: GPTConfig, cache, pages, length,
                  mesh=None):
    """Bucketed paged prefill: one dense causal pass over the bucket-padded
    prompt that writes KV into the slot's pages and returns logits at the last
    REAL position (right padding is sound under causal attention: position
    length-1 never attends to the padded tail).

    input_ids [B, Sb] right-padded to the bucket; pages [B, Sb // page_size]
    page ids (entries past the slot's reserved pages are the null page 0);
    length [B] int32 real prompt lengths.  Pool positions >= length hold
    padding garbage — masked by length during decode, overwritten as decode
    appends real tokens.  mesh: tensor-parallel over 'mp' (see
    `_paged_chunk_hidden`); the dense flash attention runs per-shard over the
    local head slice.  Returns (logits [B, V], cache).
    """
    c = config
    assert c.causal, "KV-cache decoding requires a causal model"
    B, Sb = input_ids.shape
    D, H, KVH, hd = c.hidden_size, c.num_heads, c.kv_heads, c.head_dim
    page = cache["k"].shape[2]
    n_chunks = Sb // page
    quant = "k_scale" in cache
    pin = serving_mp_constraint(mesh)
    parts = _mesh_mp(mesh)
    x = _embed(params, input_ids, c, mesh=mesh)
    if not c.use_rope:
        x = x + params["wpe"][:Sb]

    def attn_call(q, k, v):
        if pin is None:
            return flash_attention_fused(q, k, v, causal=True)
        # attention never mixes heads: run the (Pallas or XLA) flash body
        # per-shard on each chip's head slice — same trick as the paged lanes
        from ..incubate.kernels.paged_attention import _head_spec
        hs = _head_spec(4)
        return jax.shard_map(
            lambda a, b, d: flash_attention_fused(a, b, d, causal=True),
            mesh=mesh, axis_names={"mp"}, in_specs=(hs, hs, hs),
            out_specs=hs)(q, k, v)

    def layer(bp, x, kv, base):             # kv: the flat pool [L*P, ...]
        q, k, v = _prefill_qkv(bp, x, c, parts=parts)
        if pin:
            q, k, v = pin(q, "heads"), pin(k, "heads"), pin(v, "heads")
        # the dense in-chunk attention below reads the FULL-precision k/v —
        # only the pool write quantizes, so a one-shot prompt's own logits
        # see zero KV quantization error (it lands on later readers)
        wk, wv = k, v
        rows = base + pages                 # whole pages of this layer
        if quant:
            wk, ks = _quantize_kv(k)
            wv, vs = _quantize_kv(v)
            kv = dict(
                kv,
                k_scale=kv["k_scale"].at[rows].set(
                    ks.reshape(B, n_chunks, page, KVH)),
                v_scale=kv["v_scale"].at[rows].set(
                    vs.reshape(B, n_chunks, page, KVH)))
        kv = dict(kv,
                  k=kv["k"].at[rows].set(wk.reshape(B, n_chunks, page, KVH,
                                                    hd)),
                  v=kv["v"].at[rows].set(wv.reshape(B, n_chunks, page, KVH,
                                                    hd)))
        if KVH != H:
            k = jnp.repeat(k, H // KVH, axis=2)
            v = jnp.repeat(v, H // KVH, axis=2)
        attn = attn_call(q, k, v).reshape(B, Sb, H * hd)
        x = _layer_tail(bp, x, attn, c, pin)
        return x, kv

    x, new_cache = _scan_paged_layers(params, x, cache, layer)
    x = x[jnp.arange(B), length - 1]                 # last real position
    x = epilogue(params, x, c)
    return head_logits(x, params, c, mesh=mesh), new_cache


def _paged_chunk_hidden(params, input_ids, config: GPTConfig, cache,
                        page_table, q_offset, valid, mesh=None):
    """Shared trunk of the q_offset-masked paged passes (`serve_step_paged`
    and `prefill_chunk_paged`): embed a [B, C] token chunk starting at
    per-slot absolute position q_offset, write its KV token-granularly at
    page_table[(q_offset+t) // page][(q_offset+t) % page] (padded tail rows
    t >= valid route to the reserved null page 0), and attend through the page
    table to everything already written below it.

    mesh (an 'mp' axis > 1) runs the pass tensor-parallel: qkv/fc1 column- and
    proj/fc2 row-sharded (`parallel.hybrid.serving_param_specs`), the page
    pool sharded on its KVH axis (each chip holds num_heads/mp heads of every
    page), attention head-sharded per chip; page tables and offsets stay
    replicated host state.

    Returns (hidden states [B, C, D] BEFORE the final norm/head — callers
    pick their positions — and the updated cache)."""
    from ..incubate.kernels.paged_attention import paged_prefill_attention
    c = config
    assert c.causal, "KV-cache decoding requires a causal model"
    B, C = input_ids.shape
    D = c.hidden_size
    page = cache["k"].shape[2]
    quant = "k_scale" in cache
    pin = serving_mp_constraint(mesh)
    parts = _mesh_mp(mesh)
    pos = q_offset[:, None] + jnp.arange(C)                  # [B, C]
    real = jnp.arange(C)[None, :] < valid[:, None]           # [B, C]
    x = _embed(params, input_ids, c, mesh=mesh)
    if not c.use_rope:
        # jnp.take clips padded-tail positions past wpe; their rows are junk
        # the scheduler never reads (rows >= valid are never consumed)
        x = x + jnp.take(params["wpe"], pos, axis=0)
    pidx = jnp.take_along_axis(page_table, pos // page, axis=1)
    pidx = jnp.where(real, pidx, 0)                          # pad -> null page
    off = pos % page
    # what the attention is told: a null row (an inactive slot, whatever its
    # `valid`) holds no real query, so the kernel's walk skips it whole
    n_real = jnp.where(page_table[:, 0] != 0, valid, 0)

    def layer(bp, x, kv, base):             # kv: the flat pool [L*P, ...]
        # the named scopes are metadata for a profiler trace (kv_write: the
        # pool update; attn: qkv projection + paged attention; mlp: the
        # block's tail, out-projection and FFN); the program is unchanged
        with jax.named_scope("attn"):
            q, k, v = _prefill_qkv(bp, x, c, pos=pos, parts=parts)
            if pin:
                q, k, v = pin(q, "heads"), pin(k, "heads"), pin(v, "heads")
        with jax.named_scope("kv_write"):
            rows = base + pidx              # after the pad -> null-page route
            if quant:
                k, ks = _quantize_kv(k)
                v, vs = _quantize_kv(v)
                kv = dict(kv, k_scale=kv["k_scale"].at[rows, off].set(ks),
                          v_scale=kv["v_scale"].at[rows, off].set(vs))
            kv = dict(kv, k=kv["k"].at[rows, off].set(k),   # token-granular
                      v=kv["v"].at[rows, off].set(v))
        with jax.named_scope("attn"):
            attn = paged_prefill_attention(
                q, kv["k"], kv["v"], page_table + base, q_offset, n_real,
                mesh=mesh, kv_scales=_kv_scales(kv))
        with jax.named_scope("mlp"):
            x = _layer_tail(bp, x, attn.reshape(B, C, -1), c, pin)
        return x, kv

    return _scan_paged_layers(params, x, cache, layer)


def prefill_chunk_paged(params, input_ids, config: GPTConfig, cache,
                        page_table, q_offset, valid, mesh=None):
    """Chunked paged prefill (Sarathi-style, Agrawal et al. OSDI 2024): one
    dense pass over a fixed-size chunk of the prompt starting at position
    q_offset, attending through the page table to everything already written
    below it (prefix-cached pages and earlier chunks).  ONE compiled
    executable serves every chunk of every prompt — q_offset, valid and the
    page ids are all data, not shape.

    input_ids [B, C] right-padded chunk; page_table [B, max_pages] the slot's
    FULL table row; q_offset [B] int32 absolute position of input_ids[:, 0];
    valid [B] int32 real tokens in the chunk (>= 1).  KV is written
    token-granularly at page_table[(q_offset+t) // page][(q_offset+t) % page]
    — unlike the bucketed `prefill_paged`'s whole-page writes, this never
    clobbers the head of a copy-on-write page the chunk starts inside, and
    padded tail tokens route to the reserved null page 0.  Returns
    (logits [B, V] at chunk index valid-1 — the caller uses them only for the
    final chunk — and the updated cache).
    """
    B = input_ids.shape[0]
    x, cache = _paged_chunk_hidden(params, input_ids, config, cache,
                                   page_table, q_offset, valid, mesh=mesh)
    x = x[jnp.arange(B), valid - 1]                  # last real chunk position
    x = epilogue(params, x, config)
    return head_logits(x, params, config, mesh=mesh), cache


def serve_step_paged(params, tokens, cache, page_table, q_offset, valid,
                     config: GPTConfig, key=None, greedy=None, *,
                     sample: bool = False, temperature=1.0, top_k=None,
                     mesh=None):
    """The fused serving step: decode, spec-verify and an interleaved prefill
    chunk ride ONE fixed-shape executable, and sampling + greedy acceptance
    run on device — the host fetches a small `[B, T] + [B]` int token/accept
    buffer instead of `[B, V]` logits (the reference's single-graph
    `AnalysisPredictor::ZeroCopyRun` step, Sarathi-style piggybacking).

    Per-slot contract (mode is implied by the scheduler's inputs, not a
    device lane):
    - decode slot:  tokens[b, 0] = last emitted token, valid[b] = 1,
      q_offset[b] = tokens already cached;
    - verify slot:  tokens[b, 1:1+K] = drafted continuation (Leviathan et
      al. 2023), valid[b] = 1+K: token t sits at position q_offset[b] + t,
      candidate KV is written into the slot's reserved pages, and the host
      rolls rejected positions back by NOT advancing its length past the
      accepted prefix — the stale KV is overwritten when decode reaches
      those positions again;
    - chunk slot:   tokens[b, :n] = the next prompt chunk, valid[b] = n,
      q_offset[b] = prompt tokens already in pages (`prefill_chunk_paged`
      semantics — only the final chunk's pick is consumed);
    - inactive:     null page-table row, valid[b] = 1 (garbage the scheduler
      ignores).

    Returns (out_tokens [B, T] int32, accept [B] int32, cache, key):
    `out_tokens[b, t]` is the greedy prediction after position t, except
    position valid-1 where sampled (greedy[b]=False) slots carry the
    temperature/top-k pick instead — so a decode slot's token is
    `out[b, 0]`, a finished chunk's first token is `out[b, valid-1]`, and a
    verify slot emits `out[b, :accept[b]+1]` (accepted drafted prefix, which
    equals the predictions it matched, plus the bonus token).  `accept[b]` is
    the on-device greedy longest-prefix match length over the drafted tokens
    (0 for undrafted slots).  `key` advances by one split iff `sample`.
    """
    x, cache = _paged_chunk_hidden(params, tokens, config, cache,
                                   page_table, q_offset, valid, mesh=mesh)
    with jax.named_scope("head"):
        x = epilogue(params, x, config)
        logits = head_logits(x, params, config, mesh=mesh)  # [B,T,V] (V/mp ea.)
        out = sharded_argmax(logits, mesh)                    # [B, T]
    B, T = tokens.shape
    rows = jnp.arange(B)
    if sample:
        # one batched pick at each slot's last real position, through the ONE
        # shared sampling implementation (`sample_token` split-key
        # discipline); the greedy mask routes temperature=0.0 requests to the
        # argmax already in `out`, so their tokens stay PRNG-independent
        ids, key = sample_token(logits[rows, valid - 1], key, sample=True,
                                temperature=temperature, top_k=top_k,
                                mesh=mesh)
        pick = jnp.where(greedy, out[rows, valid - 1], ids)
        out = out.at[rows, valid - 1].set(pick)
    # greedy longest-prefix acceptance, on device: drafted token t+1 is
    # accepted iff it equals the prediction after position t and every
    # earlier draft was accepted (cumprod); positions past the draft
    # (t >= valid-1) never match.  Sampled slots carry no draft (valid=1),
    # so the fold at valid-1 above cannot perturb the scan.
    match = (tokens[:, 1:] == out[:, :-1]) & \
        (jnp.arange(T - 1)[None, :] < (valid - 1)[:, None])
    accept = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                     axis=1).astype(jnp.int32)
    return out, accept, cache, key


def swap_out_pages(cache, page_ids):
    """Swap-out / spill gather (vLLM-style KV swapping): copy pages out of
    the pool into a standalone device buffer the host fetches beside the
    steps that follow — the gather is a fresh buffer, so the pool pages can
    be handed to a new owner immediately.

    cache: the pool's lanes by name, each [L, P, page, ...] ({"k","v"}
    [L, P, page, KVH, hd], the scale lanes of an int8 pool beside them, or
    the one latent lane {"c"} [L, P, page, width]); page_ids [W] int32 — one
    PIECE of the pages that leave, W fixed by the engine from the page's
    bytes (`LLMEngine._swap_w`); the engine's program calls this once a
    piece over a slot's width of ids padded with the null page 0, so ONE
    fixed-shape executable serves every page count, and fetches only the
    pieces that hold wanted pages: what crosses the link is the page count
    rounded up to W (padding rows carry null-page garbage the host
    discards).  Returns the same lanes, each [L, W, page, ...].

    A page is one contiguous [page, ...] block a layer, so each is taken
    with one `dynamic_slice` and the W of them concatenated: the program
    touches the W pages and nothing else, at every pool shape.
    (`a[:, page_ids]` is the same result, but what XLA's gather does on a
    TPU depends on the operand's shape in ways this function cannot see:
    for the 640-wide latent lane at 13 layers it first copied the WHOLE
    lane out by columns — `mini-gather-slice` [0:256], [256:512], [512:640]
    — 13.9 GB accessed and 2.65 GB of temporaries a call at the xing4
    cell's shape, 20.6 ms on the chip whatever the ids, against 0.43 ms for
    this form; the same lane at 6 layers, or a dense K/V pool, it takes
    whole.)"""
    return {n: jnp.concatenate(
        [jax.lax.dynamic_slice_in_dim(a, page_ids[j], 1, axis=1)
         for j in range(page_ids.shape[0])], axis=1)
        for n, a in cache.items()}


def swap_in_pages(cache, page_ids, data):
    """Preemption swap-in scatter: restore a previously swapped victim's KV
    into its freshly allocated pages.  page_ids is a slot's capacity wide,
    padded with the null page 0 — padding rows scatter zeros into page 0,
    which is written by every inactive slot anyway and never read.  `data`
    is the pool-keyed staging dict (`{"k", "v"}`, plus the scale lanes on a
    quantized pool — int8 pages swap as int8, which is what halves the
    JXP009 host-pool pressure).  The pool arrives donated (in-place
    restore); returns the updated cache."""
    return {n: a.at[:, page_ids].set(data[n]) for n, a in cache.items()}


# LRU-bounded executable cache for `generate` (unbounded it leaks one compiled
# program per (config, B, Tp, max_new, sampling) combination — a real leak
# under varied prompt shapes; the serving engine bounds shapes by bucketing
# instead, see inference/engine.py).
GENERATE_CACHE_MAX = 16
_generate_cache: "OrderedDict[Any, Any]" = OrderedDict()
_generate_compiles = 0


def generate_cache_stats():
    """{'size', 'compiles', 'max_size'} — benches/tests assert on `compiles`
    to catch shape-churn recompilation regressions."""
    return {"size": len(_generate_cache), "compiles": _generate_compiles,
            "max_size": GENERATE_CACHE_MAX}


def sample_token(logits, key, *, sample, temperature, top_k, mesh=None):
    """Greedy argmax or temperature/top-k sample over [B, V] logits.

    The ONE sampling implementation shared by `generate` and the serving
    engine (`inference.engine.LLMEngine`) so their outputs cannot drift.
    `temperature` may be a traced scalar.  Returns (ids [B] int32, key).

    The categorical draw is written as the gumbel-argmax identity
    (`categorical(key, lg) == argmax(lg + gumbel(key, lg.shape))` — the same
    construction jax.random.categorical uses) so the mp1 and vocab-sharded
    paths are the SAME math on the same noise: under an mp mesh (logits
    arrive [.., V/mp]-sharded from `head_logits`) the full-width noise is
    deterministic per (key, element) regardless of sharding, each chip adds
    the slice it owns, a top-k threshold merges per-chip local top-ks (one
    k·mp-scalar all-gather per row — never the logits), and the pick is the
    deterministic (value, global index) merge of `sharded_argmax`.  Fixed
    key ⇒ byte-identical ids across mp∈{1,2,4} by construction."""
    if sample:
        key, sub = jax.random.split(key)
        lg = logits / temperature
        noise = jax.random.gumbel(sub, lg.shape, lg.dtype)
        if _mesh_mp(mesh) <= 1:
            if top_k:
                kth = jax.lax.top_k(lg, top_k)[0][:, -1:]
                lg = jnp.where(lg < kth, -1e30, lg)
            return jnp.argmax(lg + noise, axis=-1).astype(jnp.int32), key

        from jax.sharding import PartitionSpec as P
        V = lg.shape[-1]
        kk = int(top_k) if top_k else 0

        def local(lg_l, nz_l):
            r = jax.lax.axis_index("mp")
            Vl = lg_l.shape[-1]
            if kk:
                mine = jax.lax.top_k(lg_l, min(kk, Vl))[0]
                allk = jax.lax.all_gather(mine, "mp", axis=-1, tiled=True)
                kth = jax.lax.top_k(allk, kk)[0][:, -1:]
                lg_l = jnp.where(lg_l < kth, -1e30, lg_l)
            g = lg_l + nz_l
            lv = jnp.max(g, axis=-1)
            li = jnp.argmax(g, axis=-1).astype(jnp.int32) + r * Vl
            gm = jax.lax.pmax(lv, "mp")
            cand = jnp.where(lv == gm, li, V)
            return jax.lax.pmin(cand, "mp").astype(jnp.int32)

        ids = jax.shard_map(
            local, mesh=mesh, axis_names={"mp"},
            in_specs=(P(None, "mp"), P(None, "mp")), out_specs=P())(lg, noise)
        return ids, key
    return sharded_argmax(logits, mesh), key


def generate(params, input_ids, config: GPTConfig, max_new_tokens: int = 32,
             temperature: float = 0.0, top_k: Optional[int] = None,
             eos_token_id: Optional[int] = None, key=None):
    """Greedy / temperature sampling with a KV cache: one batched prefill
    pass, then a decode lax.scan — the WHOLE loop is one cached jitted
    program (repeat calls with the same shapes reuse the executable).
    Sequences that emit eos_token_id are frozen at EOS from then on.

    input_ids [B, T_prompt] int32 -> [B, T_prompt + max_new_tokens].
    """
    B, Tp = input_ids.shape
    total = Tp + max_new_tokens
    if not config.use_rope and total > config.max_seq_len:
        raise ValueError(
            f"prompt {Tp} + max_new_tokens {max_new_tokens} exceeds "
            f"max_seq_len {config.max_seq_len} (learned positions)")
    if key is None:
        key = jax.random.key(0)
    sample = bool(temperature and temperature > 0.0)

    cache_key = (dataclasses.astuple(config), B, Tp, max_new_tokens,
                 sample, top_k, eos_token_id)
    fn = _generate_cache.get(cache_key)
    if fn is not None:
        _generate_cache.move_to_end(cache_key)      # LRU touch
    else:
        def impl(params, ids, temp, key):
            kv = init_cache(config, B, total)

            def pick(logits, key_):
                return sample_token(logits, key_, sample=sample,
                                    temperature=temp, top_k=top_k)

            logits, kv = prefill(params, ids, config, kv)
            first, key = pick(logits, key)
            finished0 = (first == eos_token_id) if eos_token_id is not None \
                else jnp.zeros((B,), bool)
            tokens = jnp.concatenate(
                [ids, first[:, None],
                 jnp.zeros((B, max_new_tokens - 1), jnp.int32)], axis=1)

            def step(carry, pos):
                tokens, kv, key_, finished = carry
                tok = jax.lax.dynamic_index_in_dim(tokens, pos, axis=1,
                                                   keepdims=False)
                logits, kv = decode_step(params, tok, kv, pos, config)
                nxt, key_ = pick(logits, key_)
                if eos_token_id is not None:
                    nxt = jnp.where(finished, eos_token_id, nxt)
                    finished = finished | (nxt == eos_token_id)
                tokens = jax.lax.dynamic_update_slice_in_dim(
                    tokens, nxt[:, None], pos + 1, axis=1)
                return (tokens, kv, key_, finished), None

            if max_new_tokens > 1:
                (tokens, _, _, _), _ = jax.lax.scan(
                    step, (tokens, kv, key, finished0),
                    jnp.arange(Tp, total - 1))
            return tokens

        # tpu-lint: disable=TPL003 -- params are REUSED across generate() calls (the executable is LRU-cached); donating them would invalidate the caller's buffers
        fn = jax.jit(impl)
        global _generate_compiles
        _generate_compiles += 1
        _generate_cache[cache_key] = fn
        while len(_generate_cache) > GENERATE_CACHE_MAX:
            _generate_cache.popitem(last=False)     # evict least-recently-used
    return fn(params, jnp.asarray(input_ids, jnp.int32),
              jnp.asarray(temperature if sample else 1.0, jnp.float32), key)
