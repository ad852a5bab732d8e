"""Compiled hybrid-parallel trainer: dp × pp × mp (+ZeRO, +remat) in ONE jitted step.

This is the TPU-native answer to the reference's hybrid stack
(`fleet/meta_parallel/` DP reducer + mpu TP layers + `pipeline_parallel.py` 1F1B +
sharding optimizer):

- **dp / mp**: GSPMD.  Parameters carry NamedShardings (mp = Megatron layout: qkv/fc1
  column-split, proj/fc2 row-split, vocab-split embedding); the batch is sharded over
  dp; XLA inserts the exact allreduce/allgather/reduce-scatter set the reference codes
  by hand in mp_ops.py and the DP reducer — fused into the backward schedule.
- **pp**: a GPipe microbatch loop written with `jax.shard_map(axis_names={'pp'})` +
  `ppermute` inside the SAME jitted program — stages exchange activations over ICI
  each tick; `jax.grad` differentiates through the scan, producing the reverse
  pipeline automatically (the reference's hand-written 1F1B send/recv schedule,
  `pp_utils/p2p_communication.py`, becomes ~30 lines).
- **ZeRO stage-1**: optimizer moments get NamedShardings split over dp
  (`DygraphShardingOptimizer` parity, but it's just a sharding annotation here).
- **sp (sequence parallel)**: activations outside attention are sharded over mp on
  the sequence axis via sharding constraints when `sequence_parallel=True`.
- **remat**: `jax.checkpoint` around each block (`recompute` parity).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import gpt as gpt_mod
from ..inference.metrics import MetricsRegistry
from ..models import hybrid as hybrid_mod
from ..profiler import profiler as _prof

# Host spans of `HybridParallelTrainer.train_step`, recorded through
# `profiler.RecordEvent` only while a Profiler records (the engine's
# `ENGINE_SPANS` gate): placing the batch, and the call that launches the step
# program (it returns before the device finishes).
TRAINER_SPANS = ("trainer.shard_batch", "trainer.dispatch")
# steps whose counters may wait on the device before the oldest is read
_UNREAD_STEPS = 64
_NO_SPAN = contextlib.nullcontext()


def _no_span(name: str):
    return _NO_SPAN


@dataclasses.dataclass
class MeshConfig:
    dp: int = 1
    pp: int = 1
    sharding: int = 1            # ZeRO axis degree (ref topology.py:61 axis order)
    mp: int = 1
    ep: int = 1                  # expert-parallel degree (MoE all-to-all group)
    cp: int = 1                  # context-parallel degree (ring attention)
    vpp: int = 1                 # virtual pipeline chunks per stage (interleave)
    sharding_stage: int = 1      # ZeRO stage: 1=opt state, 2=+grads, 3=+params
    micro_batches: int = 1       # pipeline microbatches (per global step)
    sequence_parallel: bool = False
    remat: bool = False

    @property
    def size(self):
        return self.dp * self.pp * self.sharding * self.mp * self.ep * self.cp

    @property
    def zero_axis(self):
        """Axis the optimizer state shards over: the dedicated 'sharding' axis
        when present, else dp (pure-dp ZeRO-1, the round-1 behavior)."""
        if self.sharding > 1:
            return "sharding"
        return "dp" if self.dp > 1 else None


def build_mesh(cfg: MeshConfig, devices=None) -> Mesh:
    devs = np.array(devices if devices is not None else jax.devices()[:cfg.size])
    assert devs.size >= cfg.size, f"need {cfg.size} devices, have {devs.size}"
    # axis order mirrors the reference hybrid topology ["data","pipe","sharding",
    # "model"] (fleet/base/topology.py:61) with the MoE 'ep' and ring 'cp' axes
    # innermost so their all-to-all/ppermute ride adjacent ICI links
    return Mesh(devs[:cfg.size].reshape(cfg.dp, cfg.pp, cfg.sharding, cfg.mp,
                                        cfg.ep, cfg.cp),
                ("dp", "pp", "sharding", "mp", "ep", "cp"))


# ---------------------------------------------------------------------------
# sharding rules for the GPT params pytree (Megatron layout)
# ---------------------------------------------------------------------------

def gpt_param_specs(cfg: MeshConfig, model_config=None):
    pp = "pp" if cfg.pp > 1 else None
    mp = "mp" if cfg.mp > 1 else None
    ep = "ep" if cfg.ep > 1 else None
    use_bias = model_config is None or model_config.use_bias
    blocks = {
        "ln1_w": P(pp, None), "ln1_b": P(pp, None),
        "qkv_w": P(pp, None, mp),
        "proj_w": P(pp, mp, None),
        "ln2_w": P(pp, None), "ln2_b": P(pp, None),
    }
    if use_bias:
        blocks.update({"qkv_b": P(pp, mp), "proj_b": P(pp, None)})
    if model_config is not None and model_config.moe_num_experts > 0:
        # experts shard over 'ep' on the E dim (ref: experts distributed across
        # the moe_group ranks, dispatched via global_scatter) — router replicated
        blocks.update({
            "gate_w": P(pp, None, None),
            "exp_fc1_w": P(pp, ep, None, None), "exp_fc1_b": P(pp, ep, None),
            "exp_fc2_w": P(pp, ep, None, None), "exp_fc2_b": P(pp, ep, None),
        })
    else:
        blocks.update({
            "fc1_w": P(pp, None, mp),
            "fc2_w": P(pp, mp, None),
        })
        if use_bias:
            blocks.update({"fc1_b": P(pp, mp), "fc2_b": P(pp, None)})
        if model_config is not None and model_config.gated_ffn:
            # gate projection is column-split like fc1 (Megatron SwiGLU layout)
            blocks["fcg_w"] = P(pp, None, mp)
            if use_bias:
                blocks["fcg_b"] = P(pp, mp)
    specs = {
        "wte": P(mp, None),
        "blocks": blocks,
        "lnf_w": P(None), "lnf_b": P(None),
    }
    if cfg.sharding_stage >= 3 and cfg.sharding > 1:
        # ZeRO-3 / FSDP: params shard over the 'sharding' axis at rest; XLA
        # inserts the gather at each use site and the reduce-scatter on grads
        # (ref GroupShardedStage3 gather-on-demand, group_sharded_stage3.py).
        # Only the transformer blocks (the bulk of the params): fsdp-sharding the
        # vocab-sharded embedding turns the token lookup into a gather XLA's SPMD
        # partitioner can't device-group (CHECK crash at dp>1), the standard
        # exclude-embeddings-from-FSDP caveat.
        specs["blocks"] = _add_axis_everywhere(blocks, "sharding")
    return specs


def serving_mesh(mp: int, devices=None) -> Mesh:
    """1-D tensor-parallel mesh for the serving engine: the first `mp` devices
    on an ("mp",) axis — the decode path has no batch/pipeline dimension worth
    sharding (num_slots is small and latency-critical), so serving uses a pure
    Megatron mp slice of the machine."""
    devs = np.array(devices if devices is not None else jax.devices()[:mp])
    assert devs.size >= mp, f"need {mp} devices for mp serving, have {devs.size}"
    return Mesh(devs[:mp], ("mp",))


def serving_param_specs(model_config, params):
    """PartitionSpec tree (congruent with `params`) for tensor-parallel
    serving: the trainer's Megatron block layout (`gpt_param_specs` with the
    pp/ep axes off — qkv/fc1/fcg column-split, proj/fc2 row-split) over an
    ("mp",) serving mesh, with the embedding table and LM head VOCAB-SHARDED
    (`wte` rows / `lm_head` columns split over "mp", the Megatron
    vocab-parallel layout — ref fleet/layers/mpu.py).

    The vocab shard is what retires the repo's last replicated-memory
    ceiling: since the fused step samples ON DEVICE, the head never needs
    replicated [B, V] logits — the embed runs as a masked local take + psum
    (`models.gpt._embed`, mirroring the trainer's `_vp_embed`), the head
    matmul consumes the local shard producing [.., V/mp] logits, and the
    argmax/top-k/sample pick merges per-shard (value, global index) pairs
    (`models.gpt.sharded_argmax` / `sample_token`).  Only the tiny
    position/norm vectors (wpe, lnf) remain replicated.

    Weight-quantized params (`quantization.serving.quantize_serving_params`)
    replace a weight with the `name_q` (int8) + `name_scale` (f32) pair: the
    int8 leaf keeps the fp weight's spec, and the scale shards WITH the
    weight's quantization channel dim — block scales are [L, 1, out] and
    split with column-parallel outputs (qkv/fc1/fcg), replicated for
    row-parallel proj/fc2; the head pairs shard with their vocab dim
    (`wte_scale` [V, 1] rows, `lm_head_scale` [1, V] columns), so dequant
    stays a shard-local elementwise multiply."""
    base = gpt_param_specs(MeshConfig(mp=2), model_config)["blocks"]

    def block_spec(k):
        if k.endswith("_q"):
            return base.get(k[:-2], P())
        if k.endswith("_scale"):
            wspec = base.get(k[:-len("_scale")], P())
            last = wspec[2] if len(wspec) > 2 else None
            return P(None, None, "mp") if last is not None else P()
        return base.get(k, P())

    vocab = {
        # wte is [V, D] row-sharded; its int8 twin and [V, 1] scale follow.
        "wte": P("mp", None), "wte_q": P("mp", None),
        "wte_scale": P("mp", None),
        # untied lm_head is [D, V] column-sharded; scale is [1, V].
        "lm_head": P(None, "mp"), "lm_head_q": P(None, "mp"),
        "lm_head_scale": P(None, "mp"),
    }
    blocks = {k: block_spec(k) for k in params["blocks"]}
    specs = {k: vocab.get(k, P()) for k in params if k != "blocks"}
    specs["blocks"] = blocks
    return specs


def qkv_partition_perm(model_config, parts: int) -> np.ndarray:
    """Column permutation taking the packed `[q | k | v]` qkv layout to the
    per-partition `[q_0 k_0 v_0 | q_1 k_1 v_1 | ...]` layout whose `parts`
    contiguous column groups are exactly each mp shard's head slices.

    The trainer packs qkv as one [D, (H + 2*KVH) * hd] matmul with q, k, v
    column groups laid out globally — under the serving spec
    P(None, None, "mp") a contiguous split then lands q/k/v FRAGMENTS on
    each chip and GSPMD must stage a replicate→reslice to reassemble the
    per-head layout at the split points (ROADMAP item-3c's named blocker).
    Permuting columns once at placement time makes the contiguous shard r
    hold precisely [q_r | k_r | v_r]; the model-side unpack
    (`models.gpt._unpack_qkv`) is partition-aware and restores GLOBAL head
    order bit-exactly, so the permutation is invisible to outputs."""
    H = model_config.num_heads
    KVH = model_config.kv_heads
    hd = model_config.head_dim
    assert H % parts == 0 and KVH % parts == 0, (H, KVH, parts)
    q = np.arange(H * hd).reshape(parts, -1)
    k = H * hd + np.arange(KVH * hd).reshape(parts, -1)
    v = (H + KVH) * hd + np.arange(KVH * hd).reshape(parts, -1)
    return np.concatenate([q, k, v], axis=1).reshape(-1)


def pack_qkv_partitions(params, model_config, parts: int):
    """Permute every packed-qkv leaf (fp weight, bias, int8 twin + channel
    scale) into the per-partition column layout (`qkv_partition_perm`), so
    `device_put` under `serving_param_specs` lands each chip's qkv shard
    without replicate→reslice staging.  `parts <= 1` is the identity."""
    if parts <= 1:
        return params
    perm = qkv_partition_perm(model_config, parts)
    blocks = dict(params["blocks"])
    for k in ("qkv_w", "qkv_b", "qkv_w_q", "qkv_w_scale"):
        if k in blocks:
            blocks[k] = blocks[k][..., perm]
    out = dict(params)
    out["blocks"] = blocks
    return out


def _add_axis(spec: P, shape, axis_name: str, degree: int) -> P:
    """Shard `axis_name` onto the first unsharded, divisible dim of `shape`."""
    flat = [a for e in spec if e is not None
            for a in (e if isinstance(e, tuple) else (e,))]
    if axis_name in flat:
        return spec  # already sharded over this axis (e.g. ZeRO-3 params)
    spec_l = list(spec) + [None] * (len(shape) - len(spec))
    for i, (s, cur) in enumerate(zip(shape, spec_l)):
        if cur is None and s % degree == 0 and s >= degree:
            spec_l[i] = axis_name
            break
    return P(*spec_l)


def _add_axis_everywhere(specs, axis_name):
    """Mark specs for late binding: actual dim choice needs shapes, resolved in
    the trainer where param shapes are known."""
    return jax.tree_util.tree_map(lambda sp: ("__add__", axis_name, sp), specs,
                                  is_leaf=lambda x: isinstance(x, P))


def _resolve_spec(marked, shape, cfg: MeshConfig):
    if isinstance(marked, tuple) and len(marked) == 3 and marked[0] == "__add__":
        _, axis_name, sp = marked
        return _add_axis(sp, shape, axis_name, cfg.sharding)
    return marked


def _opt_state_spec(param_spec: P, shape, cfg: MeshConfig):
    """ZeRO-1: shard optimizer moments over the zero axis on the first dim that is
    unsharded and divisible (ref DygraphShardingOptimizer owner assignment)."""
    axis = cfg.zero_axis
    if cfg.sharding_stage < 1 or axis is None:
        return param_spec
    degree = cfg.sharding if axis == "sharding" else cfg.dp
    return _add_axis(param_spec, shape, axis, degree)


# ---------------------------------------------------------------------------
# flash attention under a partitioned step
# ---------------------------------------------------------------------------

_BATCH_AXES = ("dp", "sharding", "ep")


def _flash_per_shard(mesh, config, manual=()):
    """`attn_impl` for `block_forward` when the step is partitioned over more
    than one device: tells the flash entry which mesh axes q/k/v split over
    — batch over the data axes, heads over mp — so its Mosaic kernels run
    per shard (GSPMD cannot partition them).  Mosaic lowers only where
    EVERY mesh axis is manual, size-1 axes included.  `manual` names the axes
    the caller is already manual over (the pp loop): the region covers the
    rest and resolves against the context mesh."""
    from ..incubate.kernels.flash_attention import flash_attention_fused

    batch = tuple(a for a in _BATCH_AXES if a not in manual)
    shard = (None if manual else mesh,
             frozenset(mesh.axis_names) - frozenset(manual),
             P(batch or None, None, "mp", None))
    return functools.partial(flash_attention_fused, causal=config.causal,
                             shard=shard)


# ---------------------------------------------------------------------------
# expert parallelism: global_scatter/global_gather over the 'ep' axis
# ---------------------------------------------------------------------------

def _moe_local(bp_local, x_l, config, ep: int):
    """Per-ep-rank MoE FFN body: the TPU-native global_scatter/global_gather
    (ref fluid/operators/collective/global_scatter_op.cc).

    Runs INSIDE a manual 'ep' region: x_l [T_l, D] is this rank's token shard and
    bp_local holds this rank's E/ep experts (gate replicated).  Each rank routes
    its tokens into per-expert capacity buffers, a tiled `all_to_all` hands every
    expert its queue slices from all ranks, the batched expert MLP runs on the
    owner, and the reverse all-to-all returns outputs for the local combine.
    Returns (y_l, aux_local) — caller aggregates aux over ep.
    """
    from ..incubate.distributed.models.moe.dispatch import (
        capacity_slots, combine, dispatch, expert_ffn, moe_capacity, topk_gating)

    E, k = config.moe_num_experts, config.moe_topk
    assert E % ep == 0, f"experts {E} must divide over ep={ep}"
    Tl, D = x_l.shape
    C = moe_capacity(Tl, k, E, config.moe_capacity_factor)
    gate_idx, gate_val, aux = topk_gating(jnp.matmul(x_l, bp_local["gate_w"]), k)
    slot, keep = capacity_slots(gate_idx, E, C)
    buf = dispatch(x_l, slot, E, C)                       # [E, C, D]
    if ep > 1:
        # global_scatter: chunk j (experts j*El..) -> rank j; received chunks
        # stack along capacity, source-rank-major -> [E/ep, ep*C, D]
        buf = jax.lax.all_to_all(buf, "ep", split_axis=0, concat_axis=1,
                                 tiled=True)
    out = expert_ffn(buf, bp_local["exp_fc1_w"], bp_local["exp_fc1_b"],
                     bp_local["exp_fc2_w"], bp_local["exp_fc2_b"],
                     config.activation)
    if ep > 1:
        # global_gather: return each rank its C-slice of every expert queue
        out = jax.lax.all_to_all(out, "ep", split_axis=1, concat_axis=0,
                                 tiled=True)              # [E, C, D]
    y = combine(out, slot, keep, gate_val)
    return y, aux


_MOE_EXPERT_KEYS = ("exp_fc1_w", "exp_fc1_b", "exp_fc2_w", "exp_fc2_b")


def _moe_ffn_ep(bp, x, config, cfg: MeshConfig, mesh):
    """GSPMD-path wrapper: shard_map the manual 'ep' MoE body over x [T, D]."""

    def local(gate_w, f1w, f1b, f2w, f2b, x_l):
        bp_local = {"gate_w": gate_w, "exp_fc1_w": f1w, "exp_fc1_b": f1b,
                    "exp_fc2_w": f2w, "exp_fc2_b": f2b}
        y, aux = _moe_local(bp_local, x_l, config, cfg.ep)
        return y, jax.lax.psum(aux, "ep") / cfg.ep

    return jax.shard_map(
        local, mesh=mesh, axis_names={"ep"},
        in_specs=(P(), P("ep"), P("ep"), P("ep"), P("ep"), P("ep")),
        out_specs=(P("ep"), P()))(
            bp["gate_w"], bp["exp_fc1_w"], bp["exp_fc1_b"],
            bp["exp_fc2_w"], bp["exp_fc2_b"], x)


# ---------------------------------------------------------------------------
# context-parallel loss: sequence sharded over 'cp', ring attention inside
# ---------------------------------------------------------------------------

def _cp_loss(params, tokens, labels, config, cfg: MeshConfig, mesh):
    """Long-context training: tokens/labels [B, S] with S sharded over 'cp';
    every block's attention runs the ring (SURVEY §7.10 — beyond-reference)."""
    import functools

    from .ring_attention import ring_attention_local

    cp = cfg.cp
    B, S = tokens.shape
    Sl = S // cp
    assert S % cp == 0, f"seq len {S} must divide over cp={cp}"
    attn = functools.partial(ring_attention_local, axis_name="cp", cp=cp,
                             causal=True)

    # embedding + LM head run OUTSIDE the manual cp region so the existing
    # vocab-parallel shard_maps handle the mp-sharded table (a vocab-sharded
    # gather under auto axes CHECK-crashes XLA's partitioner)
    x = _vp_embed(params["wte"], tokens, mesh, cfg)
    if not config.use_rope:
        x = x + params["wpe"][:S]

    def local(blocks, lnf_w, lnf_b, x_l):
        r = jax.lax.axis_index("cp")
        offset = r * Sl
        x_l, aux = gpt_mod.run_blocks(blocks, x_l, config, remat=cfg.remat,
                                      attn_impl=attn, pos_offset=offset)
        h = gpt_mod._norm(x_l, lnf_w, lnf_b, config)
        return h, jax.lax.psum(aux, "cp")

    blk_specs = jax.tree_util.tree_map(lambda _: P(), params["blocks"])
    h, aux = jax.shard_map(
        local, mesh=mesh, axis_names={"cp"},
        in_specs=(blk_specs, P(), P(), P(None, "cp", None)),
        out_specs=(P(None, "cp", None), P()))(
            params["blocks"], params["lnf_w"], params["lnf_b"], x)
    head = params["wte"].T if config.tie_word_embeddings else params["lm_head"]
    loss = _vp_ce(h, head, labels, mesh, cfg)
    if config.moe_num_experts > 0:
        # psum summed cp per-shard aux values; mean matches the dense scale
        loss = loss + config.moe_aux_weight * aux / cp
    return loss


# ---------------------------------------------------------------------------
# pipeline loop (manual over 'pp', GSPMD over dp/mp)
# ---------------------------------------------------------------------------

def _vp_embed(wte, tokens, mesh, cfg: MeshConfig):
    """Vocab-parallel embedding (ref VocabParallelEmbedding, mp_layers.py:35):
    masked local lookup on the mp-sharded table + psum.  Keeps the gather fully
    local so XLA's SPMD partitioner never sees a vocab-sharded gather (which it
    CHECK-crashes on at 4 live mesh axes)."""
    if cfg.mp <= 1:
        return jnp.take(wte, tokens, axis=0)

    def local(wte_l, tok):
        r = jax.lax.axis_index("mp")
        Vl = wte_l.shape[0]
        ids = tok - r * Vl
        ok = (ids >= 0) & (ids < Vl)
        safe = jnp.clip(ids, 0, Vl - 1)
        e = jnp.take(wte_l, safe, axis=0)
        e = jnp.where(ok[..., None], e, jnp.zeros((), e.dtype))
        return jax.lax.psum(e, "mp")

    return jax.shard_map(local, mesh=mesh, axis_names={"mp"},
                         in_specs=(P("mp", None), P()),
                         out_specs=P())(wte, tokens)


def _vp_ce(h, head, labels, mesh, cfg: MeshConfig):
    """Cross entropy with the vocab dim mp-sharded and (when divisible) the batch
    dim pp-sharded — every device computes head flops exactly once per token (ref
    ParallelCrossEntropy, mp_layers.py:524)."""
    manual = set()
    batch_axes = ()
    if cfg.pp > 1 and h.shape[0] % cfg.pp == 0:
        manual.add("pp")
        batch_axes = ("pp",)
        # with an ep axis live, leaving it auto makes XLA's gather partitioner
        # CHECK-crash on the label pick; fold it into the manual batch split,
        # or fall back to the dense CE when the batch doesn't divide
        if cfg.ep > 1:
            if h.shape[0] % (cfg.pp * cfg.ep) == 0:
                manual.add("ep")
                batch_axes = ("pp", "ep")
            else:
                manual.discard("pp")
                batch_axes = ()
    # cp shards the SEQUENCE dim; like ep, leaving it auto crashes the gather
    # partitioner when another manual axis is live
    seq_axes = ()
    if cfg.cp > 1 and "pp" in manual and h.shape[1] % cfg.cp == 0:
        manual.add("cp")
        seq_axes = ("cp",)
    if cfg.mp > 1:
        manual.add("mp")
    if not manual:
        loss_sum, n = gpt_mod._ce_sums(jnp.matmul(h, head), labels)
        return loss_sum / jnp.maximum(n, 1.0)

    have_mp = "mp" in manual

    def local(h_l, head_l, lab_l):
        logits = jnp.matmul(h_l, head_l).astype(jnp.float32)  # [b_l, S, V_l]
        # max shift is stability-only and cancels out of lse - pick; stop_gradient
        # also sidesteps pmax's missing differentiation rule
        mx = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
        if have_mp:
            mx = jax.lax.pmax(mx, "mp")
        se = jnp.sum(jnp.exp(logits - mx[..., None]), axis=-1)
        if have_mp:
            se = jax.lax.psum(se, "mp")
        lse = mx + jnp.log(se)
        if have_mp:
            r = jax.lax.axis_index("mp")
            Vl = head_l.shape[-1]
            ids = lab_l - r * Vl
            ok = (ids >= 0) & (ids < Vl)
            safe = jnp.clip(ids, 0, Vl - 1)
            pick = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
            pick = jax.lax.psum(jnp.where(ok, pick, 0.0), "mp")
        else:
            safe = jnp.where(lab_l < 0, 0, lab_l)
            pick = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        mask = (lab_l >= 0).astype(jnp.float32)
        ls = jnp.sum((lse - pick) * mask)
        n = jnp.sum(mask)
        if batch_axes or seq_axes:
            ls = jax.lax.psum(ls, batch_axes + seq_axes)
            n = jax.lax.psum(n, batch_axes + seq_axes)
        return ls, n

    spec_b = P(batch_axes if batch_axes else None,
               seq_axes if seq_axes else None)
    spec_head = P(None, "mp") if have_mp else P()
    ls, n = jax.shard_map(local, mesh=mesh, axis_names=manual,
                          in_specs=(spec_b, spec_head, spec_b),
                          out_specs=(P(), P()))(h, head, labels)
    return ls / jnp.maximum(n, 1.0)


def _pp_loss(params, tokens, labels, config, cfg: MeshConfig, mesh):
    """Pipeline-parallel loss: vocab-parallel embed -> microbatch loop over 'pp'
    via shard_map+ppermute -> last-stage outputs -> vocab/batch-parallel CE.

    Schedule note (ref 1F1B, pipeline_parallel.py:387): the forward is a GPipe
    sweep, but under jax.grad XLA reverses the tick scan, so backward ticks run
    newest-microbatch-first exactly like 1F1B cooldown, and per-tick residency is
    only the boundary activation stack (per-block internals rematerialize via
    run_blocks' checkpoint policy) — the 1F1B memory profile without the
    hand-written send/recv schedule.  The LM head runs once per token, sharded
    over pp (microbatches) and mp (vocab) — no per-tick head waste.

    Interleaving (cfg.vpp > 1, ref PipelineParallelWithInterleave :822): each
    stage holds vpp NON-CONTIGUOUS layer chunks (chunk c covers layers
    [c*P*Lc + p*Lc, ...]); every tick runs ONE chunk, 1/vpp of a GPipe tick, and
    the Megatron closed-form schedule (device p delayed p ticks, work order
    g-major then chunk then slot) makes every ring hand-off arrive exactly one
    tick ahead of use.  Warmup/cooldown ticks shrink from (P-1) full-stage
    ticks to (P-1) chunk ticks — the pipeline bubble drops by vpp."""
    M = cfg.micro_batches
    Ppp = cfg.pp
    B, S = tokens.shape
    assert B % M == 0, \
        f"batch {B} must divide into micro_batches={M} (pad the batch; " \
        "uneven microbatches are not supported)"
    mb = B // M
    D = config.hidden_size
    # MoE with ep runs in the SAME manual region as pp (shardy requires manual
    # axes to be declared together rather than nested), so each (pp, ep) rank
    # routes its microbatch shard and all_to_all's over 'ep' inside the tick
    moe_manual = config.moe_num_experts > 0 and cfg.ep > 1
    cp_manual = cfg.cp > 1
    manual = ("pp",) + (("ep",) if moe_manual else ()) + \
        (("cp",) if cp_manual else ())
    if moe_manual:
        assert mb % cfg.ep == 0, f"microbatch {mb} must divide over ep={cfg.ep}"
    if cp_manual:
        assert not moe_manual, "cp x ep is not supported yet"
        assert S % cfg.cp == 0, f"seq len {S} must divide over cp={cfg.cp}"
    mb_l = mb // cfg.ep if moe_manual else mb
    S_l = S // cfg.cp if cp_manual else S
    moe_impl = (lambda bpl, xl, c: _moe_local(bpl, xl, c, cfg.ep)) \
        if moe_manual else None

    x = _vp_embed(params["wte"], tokens, mesh, cfg)
    if not config.use_rope:
        x = x + params["wpe"][:S]
    xs = x.reshape(M, mb, S, D)

    vpp = cfg.vpp
    if vpp > 1:
        assert M % Ppp == 0, \
            f"interleaved schedule needs micro_batches {M} % pp {Ppp} == 0"
        assert config.num_layers % (Ppp * vpp) == 0, \
            f"layers {config.num_layers} must divide over pp*vpp"
        # chunk c of stage p = layers [(c*Ppp + p) * Lc, ...): reshape the
        # stacked layer axis to [vpp, Ppp, Lc] and shard the Ppp axis.  The
        # reshape INTERLEAVES layers across the new dims, so the params' at-rest
        # (pp, ..., mp) sharding cannot be pushed through it — the partitioner
        # used to fall back to involuntary full rematerialization ([SPMD]
        # warnings in the multichip dry run).  Stage it explicitly instead:
        # allgather to replicated, reshape, reslice onto pp — each transition
        # is one the partitioner lowers efficiently.  The mp allgather is not
        # extra work: the shard_map below consumes P(None, "pp") inputs, so
        # axes outside pp were ALWAYS replicated at this boundary.
        def _vpp_reshape(a):
            a = jax.lax.with_sharding_constraint(a, NamedSharding(mesh, P()))
            a = a.reshape((vpp, Ppp, a.shape[0] // (vpp * Ppp)) + a.shape[1:])
            return jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, P(None, "pp")))

        blocks_arg = jax.tree_util.tree_map(_vpp_reshape, params["blocks"])
        T = vpp * M + Ppp - 1
    else:
        blocks_arg = params["blocks"]
        T = M + Ppp - 1

    if cp_manual:
        from .ring_attention import ring_attention_local
        attn_impl = functools.partial(ring_attention_local, axis_name="cp",
                                      cp=cfg.cp, causal=True)
    else:
        attn_impl = _flash_per_shard(mesh, config, manual)

    def local_fn(blocks_local, xs_rep):
        p = jax.lax.axis_index("pp")
        pos_offset = jax.lax.axis_index("cp") * S_l if cp_manual else None

        def tick(carry, t):
            buf, aux_acc = carry
            if vpp > 1:
                u = t - p                  # this device's schedule position
                uc = jnp.clip(u, 0, vpp * M - 1)
                g = uc // (vpp * Ppp)      # microbatch group
                r = uc % (vpp * Ppp)
                c = r // Ppp               # virtual chunk
                m = g * Ppp + (r % Ppp)    # microbatch index
                chunk = jax.tree_util.tree_map(lambda a: a[c][0], blocks_local)
                inject = (p == 0) & (c == 0)
                valid = ((u >= 0) & (u < vpp * M))
            else:
                chunk = blocks_local
                m = jnp.clip(t, 0, M - 1)
                inject = p == 0
                valid = (t >= p) & (t < p + M)
            inp = jnp.where(inject, xs_rep[m], buf)
            out, aux = gpt_mod.run_blocks(chunk, inp, config,
                                          remat=cfg.remat, moe_impl=moe_impl,
                                          attn_impl=attn_impl,
                                          pos_offset=pos_offset)
            nxt = jax.lax.ppermute(out, "pp",
                                   [(i, (i + 1) % Ppp) for i in range(Ppp)])
            # invalid (warmup/cooldown) ticks run on garbage; mask their aux
            return (nxt, aux_acc + (aux * valid.astype(aux.dtype))[None]), out

        buf0 = jax.lax.pcast(jnp.zeros((mb_l, S_l, D), xs_rep.dtype), manual,
                             to="varying")
        aux0 = jax.lax.pcast(jnp.zeros((1,), jnp.float32), manual,
                             to="varying")
        (_, aux_sum), outs = jax.lax.scan(tick, (buf0, aux0), jnp.arange(T))
        # drop warmup/cooldown garbage IN-shard: only M ticks (and their grad
        # cotangents) cross the shard_map boundary.  The finish ticks are
        # static; only the LAST stage's slice is consumed downstream, but every
        # stage must slice identically for a uniform out_spec.
        if vpp == 1:
            outs = outs[Ppp - 1:]
        else:
            finish = [(m // Ppp) * vpp * Ppp + (vpp - 1) * Ppp + (m % Ppp)
                      + Ppp - 1 for m in range(M)]
            outs = outs[np.asarray(finish)]
        return outs, jax.lax.psum(aux_sum, manual)

    if vpp > 1:
        # vpp reshape puts experts' E on dim 3: [vpp, Ppp, Lc, E, ...]
        blk_in = {k: (P(None, "pp", None, "ep") if (moe_manual and
                                                    k in _MOE_EXPERT_KEYS)
                      else P(None, "pp"))
                  for k in params["blocks"]}
    else:
        blk_in = {k: (P("pp", "ep") if (moe_manual and k in _MOE_EXPERT_KEYS)
                      else P("pp"))
                  for k in params["blocks"]}
    xs_spec = P(None, "ep" if moe_manual else None,
                "cp" if cp_manual else None)
    out_spec = P("pp", "ep" if moe_manual else None,
                 "cp" if cp_manual else None)
    f = jax.shard_map(
        local_fn, mesh=mesh, axis_names=set(manual),
        in_specs=(blk_in, xs_spec),
        out_specs=(out_spec, P()))
    stacked_all, aux_sum = f(blocks_arg, xs)   # [Ppp*M, mb, S, D]
    aux_sum = aux_sum[0]
    if moe_manual:
        aux_sum = aux_sum / cfg.ep
    # each stage contributed M sliced ticks; the last stage's hold finished
    # microbatches 0..M-1 in order
    hs = stacked_all[(Ppp - 1) * M:]           # [M, mb, S, D]
    h = gpt_mod._norm(hs.reshape(B, S, D), params["lnf_w"], params["lnf_b"],
                      config)
    head = params["wte"].T if config.tie_word_embeddings else params["lm_head"]
    loss = _vp_ce(h, head, labels, mesh, cfg)
    if config.moe_num_experts > 0:
        # aux_sum covers all M microbatches (and, with cp, all cp seq shards);
        # average to match the dense scale
        loss = loss + config.moe_aux_weight * aux_sum / (M * cfg.cp)
    return loss


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------

def _refuse_untrainable(config, mesh_cfg: MeshConfig) -> None:
    """A patterned configuration (`models.hybrid`) trains where its pattern
    is of latent attention, dense FFN and expert layers with one residual
    stream, on one device; what cannot is refused with its reason."""
    def no(why):
        raise ValueError(f"layer_pattern {config.layer_pattern!r} is served, "
                         f"not trained: {why}")
    if "M" in config.layer_pattern:
        no("the state-space layer's chunked scan (kernels.ssm) has no "
           "backward pass")
    if "*" in config.layer_pattern:
        no("the training walk has no pass for the position-free attention "
           "layer (`*`); only L, F and E are trained and tested")
    if config.hc_mult > 1:
        no(f"hc_mult {config.hc_mult}: the training walk keeps one residual "
           "stream (the hyper-connections' mixes and Sinkhorn are served "
           "only)")
    if mesh_cfg.size > 1:
        no(f"a mesh of {mesh_cfg.size} devices: a patterned configuration's "
           "parameters are replicated and its step has no exchange between "
           "chips yet (ROADMAP B1); it trains on one")


class HybridParallelTrainer:
    """Owns mesh + sharded params/opt-state + the ONE jitted train step.

    A patterned configuration (`models.hybrid.HybridConfig`: latent
    attention, dense FFN, dropless experts; see `_refuse_untrainable`) goes
    through the same step: per-layer trees, every spec replicated, AdamW over
    every leaf but `router_bias`, which `hybrid.router_bias_step` moves
    inside the same program, and the step's counters returned with the loss
    (`stats()`)."""

    def __init__(self, config: gpt_mod.GPTConfig, mesh_cfg: MeshConfig,
                 learning_rate=1e-4, weight_decay=0.01, beta1=0.9, beta2=0.95,
                 grad_clip_norm: Optional[float] = 1.0, seed=0, devices=None,
                 moment_dtype=jnp.float32):
        self.patterned = getattr(config, "layer_pattern", None) is not None
        if self.patterned:
            _refuse_untrainable(config, mesh_cfg)
        self.config = config
        self.cfg = mesh_cfg
        self.mesh = build_mesh(mesh_cfg, devices)
        self.lr = learning_rate
        self.wd = weight_decay
        self.betas = (beta1, beta2)
        self.clip_norm = grad_clip_norm
        self.moment_dtype = moment_dtype

        init_params = functools.partial(
            (hybrid_mod if self.patterned else gpt_mod).init_params, config)
        shapes = jax.eval_shape(init_params, jax.random.key(0))
        if self.patterned:
            specs = jax.tree_util.tree_map(lambda _: P(), shapes)
        else:
            specs = gpt_param_specs(mesh_cfg, config)
            if not config.use_rope:
                specs["wpe"] = P(None, None)
            if not config.tie_word_embeddings:
                specs["lm_head"] = P(None, "mp" if mesh_cfg.mp > 1 else None)
            # late-bind ZeRO-3 param sharding (needs the shapes)
            is_marked = lambda x: isinstance(x, P) or (
                isinstance(x, tuple) and len(x) == 3 and x[0] == "__add__")
            specs = jax.tree_util.tree_map(
                lambda sp, sh: _resolve_spec(sp, sh.shape, mesh_cfg), specs,
                shapes, is_leaf=is_marked)
        self.param_specs = specs
        self.param_shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))

        key = jax.random.key(seed)
        init = jax.jit(init_params, out_shardings=self.param_shardings)
        self.params = init(key)

        m_shardings = jax.tree_util.tree_map(
            lambda l, s: NamedSharding(self.mesh, _opt_state_spec(s, l.shape, mesh_cfg)),
            self.params, specs)
        self._m_shardings = m_shardings
        mdt = moment_dtype
        init_opt = jax.jit(
            lambda p: {"m": jax.tree_util.tree_map(lambda x: jnp.zeros_like(x, mdt), p),
                       "v": jax.tree_util.tree_map(lambda x: jnp.zeros_like(x, mdt), p),
                       "step": jnp.zeros((), jnp.int32)},
            out_shardings={"m": m_shardings, "v": m_shardings, "step": None})
        self.opt_state = init_opt(self.params)
        self._step_fn = self._build_step()
        self._eval_fn = None    # built lazily on first eval_loss
        self.steps_dispatched = 0   # train_step calls; numbers the step marker
        # what the step program counted, step by step, not yet read from the
        # device: `stats()` folds it into the registry (a patterned step
        # returns its counters with the loss; a dense one has none)
        self.metrics = MetricsRegistry("trainer")
        self._unread = collections.deque()
        self.last_step = None   # the newest patterned step's tree, un-synced

    # ---- sharding constraint hook handed to the model ----
    def _mp_constraint(self, x, kind):
        cfg = self.cfg
        if cfg.mp <= 1:
            return x
        if kind in ("hidden_mp", "ffn_mp"):
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, P(_BATCH_AXES, None, "mp")))
        if kind == "act" and cfg.sequence_parallel:
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, P(_BATCH_AXES, "mp", None)))
        return x

    def _build_step(self):
        config = self.config
        cfg = self.cfg
        mesh = self.mesh
        lr, wd = self.lr, self.wd
        b1, b2 = self.betas
        clip = self.clip_norm

        moe_impl = None
        if config.moe_num_experts > 0 and cfg.ep > 1:
            moe_impl = functools.partial(_moe_ffn_ep, cfg=cfg, mesh=mesh)

        if cfg.cp > 1:
            assert cfg.ep == 1, "cp x ep is not supported yet"
        if cfg.vpp > 1:
            assert cfg.pp > 1, \
                "vpp (interleaved virtual stages) requires pp > 1 (ref: " \
                "virtual_pp_degree needs pipeline parallelism)"

        attn_impl = _flash_per_shard(mesh, config) if cfg.size > 1 else None

        def loss_of(params, tokens, labels):
            if self.patterned:
                return hybrid_mod.train_loss(params, tokens, labels, config,
                                             remat=cfg.remat)
            if cfg.pp > 1:
                return _pp_loss(params, tokens, labels, config, cfg, mesh)
            if cfg.cp > 1:
                return _cp_loss(params, tokens, labels, config, cfg, mesh)
            return gpt_mod.loss_fn(params, tokens, labels, config,
                                   mp_constraint=self._mp_constraint,
                                   remat=cfg.remat, moe_impl=moe_impl,
                                   attn_impl=attn_impl)

        def step(params, opt_state, tokens, labels):
            # named scopes are metadata: fwd / bwd / opt prefix the
            # operations' names in a profiler trace, the program is the same
            # as jax.value_and_grad(loss_of)'s
            with jax.named_scope("fwd"):
                loss, pullback, *aux = jax.vjp(
                    lambda p: loss_of(p, tokens, labels), params,
                    has_aux=self.patterned)
            with jax.named_scope("bwd"):
                grads, = pullback(jnp.ones_like(loss))
            with jax.named_scope("opt"):
                params, opt_state = update(params, opt_state, grads)
            if not self.patterned:
                return loss, params, opt_state
            # the loss and what the step counted, in one tree
            aux, = aux
            out = {"loss": loss, "loss_main": aux["loss_main"],
                   "loss_mtp": aux["loss_mtp"]}
            if "load" in aux:
                params, moves = hybrid_mod.router_bias_step(
                    params, aux["load"], config)
                out.update(aux["moe"], router_bias_moves=moves)
            return out, params, opt_state

        def update(params, opt_state, grads):
            if cfg.sharding_stage >= 2 and cfg.zero_axis is not None:
                # ZeRO-2: pin grads to the moment layout so XLA reduce-scatters
                # them over the zero axis instead of all-reducing full grads
                # (ref GroupShardedStage2 reduce-to-owner)
                grads = jax.tree_util.tree_map(
                    lambda g, sh: jax.lax.with_sharding_constraint(g, sh),
                    grads, self._m_shardings)
            if clip is not None:
                gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                     for g in jax.tree_util.tree_leaves(grads)))
                scale = jnp.minimum(clip / jnp.maximum(gnorm, clip), 1.0)
                grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
            stepno = opt_state["step"] + 1
            b1p = 1 - b1 ** stepno.astype(jnp.float32)
            b2p = 1 - b2 ** stepno.astype(jnp.float32)

            mdt = self.moment_dtype

            def upd(path, p, g, m, v):
                if getattr(path[-1], "key", None) == "router_bias":
                    # not the optimizer's: no gradient reaches it (it steers
                    # the top-k choice only) and no decay; its own rule
                    # (`hybrid.router_bias_step`) moves it
                    return p, m, v
                g32 = g.astype(jnp.float32)
                m32 = b1 * m.astype(jnp.float32) + (1 - b1) * g32
                v32 = b2 * v.astype(jnp.float32) + (1 - b2) * g32 * g32
                u = (m32 / b1p) / (jnp.sqrt(v32 / b2p) + 1e-8)
                newp = p.astype(jnp.float32) * (1 - lr * wd) - lr * u
                return newp.astype(p.dtype), m32.astype(mdt), v32.astype(mdt)

            out = jax.tree_util.tree_map_with_path(
                upd, params, grads, opt_state["m"], opt_state["v"])
            new_params = jax.tree_util.tree_map(lambda t: t[0], out,
                                                is_leaf=lambda x: isinstance(x, tuple))
            new_m = jax.tree_util.tree_map(lambda t: t[1], out,
                                           is_leaf=lambda x: isinstance(x, tuple))
            new_v = jax.tree_util.tree_map(lambda t: t[2], out,
                                           is_leaf=lambda x: isinstance(x, tuple))
            return new_params, {"m": new_m, "v": new_v, "step": stepno}

        # batch splits over dp AND sharding AND ep: the zero group is a
        # data-parallel group with sharded states, and ep ranks each own a batch
        # shard whose tokens they route (ref: moe_group is a data-parallel group)
        data_sharding = NamedSharding(self.mesh, P(_BATCH_AXES, None))
        opt_sh = {"m": self._m_shardings, "v": self._m_shardings, "step": None}
        # out_shardings pinned so params stay in the param layout across steps (else
        # XLA propagates the ZeRO 'dp' shard from the moments onto updated params and
        # the next call's in_shardings check rejects them)
        return jax.jit(step, donate_argnums=(0, 1),
                       in_shardings=(self.param_shardings, opt_sh,
                                     data_sharding, data_sharding),
                       out_shardings=(None, self.param_shardings, opt_sh))

    def shard_batch(self, tokens, labels):
        ds = NamedSharding(self.mesh, P(_BATCH_AXES, None))
        return (jax.device_put(jnp.asarray(tokens), ds),
                jax.device_put(jnp.asarray(labels), ds))

    def train_step(self, tokens, labels):
        """One optimizer step; returns the loss un-synced.  While a Profiler
        records, the call sits in a `StepTraceAnnotation("train_step")`
        numbered by `steps_dispatched` and its two host phases are
        `TRAINER_SPANS`; otherwise it pays one flag read."""
        self.steps_dispatched += 1
        if _prof.is_recording():
            span = _prof.RecordEvent
            mark = jax.profiler.StepTraceAnnotation(
                "train_step", step_num=self.steps_dispatched)
        else:
            span, mark = _no_span, _NO_SPAN
        with mark:
            with span("trainer.shard_batch"):
                tokens, labels = self.shard_batch(tokens, labels)
            with span("trainer.dispatch"):
                out, self.params, self.opt_state = self._step_fn(
                    self.params, self.opt_state, tokens, labels)
        self.metrics.counter("tokens_trained").inc(tokens.size)
        if not self.patterned:
            return out
        # kept on the device until somebody asks: no sync here.  The oldest
        # is folded once many wait (its step ended long ago)
        self.last_step = out
        self._unread.append(out)
        if len(self._unread) > _UNREAD_STEPS:
            self._fold(self._unread.popleft())
        return out["loss"]

    def _fold(self, out) -> None:
        """One step's tree into the registry: sums over the expert layers
        into counters, extremes and the losses into gauges."""
        m = self.metrics
        vals = jax.device_get(out)
        for name in ("loss_main", "loss_mtp"):
            m.gauge(name).set(float(vals[name]))
        if "router_bias_moves" not in vals:
            return
        for name in ("moe_pairs_here", "moe_pairs_away", "moe_experts_touched",
                     "moe_pairs_over_bound"):
            m.counter(name).inc(int(vals[name].sum()))
        m.counter("router_bias_moves").inc(int(vals["router_bias_moves"]))
        calls = m.counter("moe_layer_calls")
        hi, lo = (m.gauge(n, agg="max") for n in ("moe_load_max",
                                                  "moe_load_min"))
        step_hi = float(vals["moe_load_max"].max())
        step_lo = float(vals["moe_load_min"].min())
        hi.set(max(hi.value, step_hi))
        lo.set(min(lo.value, step_lo) if calls else step_lo)
        calls.inc(int(vals["moe_load_max"].size))

    def stats(self) -> dict:
        """The trainer's counters as the engine's `stats()` gives its own: a
        flat dict of the registry's counters and gauges after every unread
        step is folded in (this reads the device: the last step's results).
        `train_steps`, `tokens_trained`; for a patterned configuration the
        last folded step's `loss_main` / `loss_mtp`, the expert layers' sums
        over layers and steps (`moe_pairs_here`, `moe_pairs_away`,
        `moe_experts_touched`, `moe_pairs_over_bound`, `moe_layer_calls`),
        the largest and smallest load of a held expert in any layer and step
        (`moe_load_max`, `moe_load_min`) and `router_bias_moves`."""
        while self._unread:
            self._fold(self._unread.popleft())
        snap = self.metrics.snapshot()
        return {"train_steps": self.steps_dispatched, **snap["counters"],
                **snap["gauges"]}

    def eval_loss(self, tokens, labels):
        """The loss the step trains on, without a step (a patterned
        configuration's two terms are left, un-synced, in `eval_terms`)."""
        # jitted once with the trainer's param shardings and reused — the old
        # eager loss_fn call retraced the whole model on every eval batch
        if self._eval_fn is None:
            config = self.config
            if self.patterned:
                fn = lambda p, t, l: hybrid_mod.train_loss(p, t, l, config)
            else:
                fn = lambda p, t, l: gpt_mod.loss_fn(p, t, l, config)
            self._eval_fn = jax.jit(
                fn, in_shardings=(self.param_shardings, None, None))
        out = self._eval_fn(self.params, jnp.asarray(tokens),
                            jnp.asarray(labels))
        if not self.patterned:
            return out
        loss, aux = out
        self.eval_terms = {k: aux[k] for k in ("loss_main", "loss_mtp")}
        return loss
