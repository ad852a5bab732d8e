"""The dropless expert layer, served and trained: top-k routing over ALL the
router's experts, computed for the experts this chip holds.

`dispatch.py` beside this file is the dense GPT block's training layer:
softmax gate, capacity per expert, tokens past the capacity dropped.  Dropped
tokens change logits, so serving cannot use it; and it has no notion of an
expert that lives on another chip.  This layer has no capacity and drops
nothing:

    s      = sigmoid(h W_r)                      float32, [N, E_total]
    top-k  of s + b_corr                         (the bias steers the choice only)
    w_i    = s_i / (sum_topk s + 1e-20) * routed_scaling_factor
    out    = sum_{i in top-k, i held here} w_i * expert_i(h)  +  shared(h)
    expert(h) = relu(h U)^2 D                    (`up_w` holds U^T), or, where
                the layer's tree holds gate matrices (`gate_w`, kept as G^T
                like U; `shared_gate_w`), silu(h G) * (h U) D   (SwiGLU)

`experts_here` / `expert_offset` say which experts this chip holds.  A
token-expert pair that falls on an absent expert is left out (the chip that
holds it adds that part; on one chip nothing does, and the reference is given
the same share).  The shared expert is computed once, here.  The held pairs
are sorted by expert and go through `kernels.grouped_matmul` twice (three
times when gated: gate and up are two products of the same shape).

The layer is differentiable: the grouped products bring their backward
(`kernels.grouped_matmul`), the router's scores take their gradient through
the chosen weights `w_i` (the top-k indices carry none, and so the bias takes
none: it is moved by a rule of its own, `models.hybrid.router_bias_step`).

`pair_bound`: a serving step gathers all N x k pairs, the absent experts'
too, since a step has few.  A training step at N = 32,768 and k = 8 would
gather 262,144 rows of D to compute the sixteenth of them that fell here;
with `pair_bound` only the first `pair_bound` held pairs (in expert order) are
gathered and multiplied.  The bound is static, so it can be exceeded: the
pairs past it are left out AND counted (`moe_pairs_over_bound`), and a caller
that promises a dropless layer checks that the count is nought.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ....kernels.grouped_matmul import ROW_TILE, grouped_matmul

COUNTERS = ("moe_pairs_here", "moe_pairs_away", "moe_experts_touched",
            "moe_load_max")
# what a training step reads besides (`moe_dropless(..., pair_bound=...)`)
TRAIN_COUNTERS = COUNTERS + ("moe_load_min", "moe_pairs_over_bound")


def relu2(x):
    r = jnp.maximum(x.astype(jnp.float32), 0.0)
    return r * r


def swiglu(gate, up):
    return jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)


def route(h, lp, cfg):
    """(expert ids [N, k] int32, weights [N, k] float32) of the published
    router, in float32 whatever the activations' type."""
    f32 = jnp.float32
    logits = jnp.dot(h.astype(f32), lp["router_w"].astype(f32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + lp["router_bias"].astype(f32),
                           cfg.num_experts_per_tok)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * cfg.routed_scaling_factor


def moe_dropless(lp, h, cfg, real, pair_bound=None,
                 row_tile: int = ROW_TILE):
    """h [N, D] (normed); real [N] bool, False on padding and inactive slots
    (their rows are not routed and come back as the shared expert's output,
    which nobody reads).  Returns (out [N, D] in h's dtype, {counter: int32
    scalar} over the real rows); with `pair_bound` (see the module docstring)
    the counters are `TRAIN_COUNTERS` and one more entry, "load" [E_total]:
    the pairs each of the router's experts was chosen for, held or not."""
    N, D = h.shape
    k, E_all = cfg.num_experts_per_tok, cfg.n_routed_experts
    first, E = cfg.expert_offset, cfg.experts_here
    with jax.named_scope("router"):
        idx, w = route(h, lp, cfg)
        held_id = (idx >= first) & (idx < first + E)
        here = held_id & real[:, None]
        away = ~held_id & real[:, None]
        # one row per token-expert pair, sorted by expert; pairs nobody
        # computes here (absent expert, padding) sort past the last group
        gid = jnp.where(here, idx, E_all).reshape(-1)
        sizes = jnp.zeros((E_all + 1,), jnp.int32).at[gid].add(1)[:E_all]
        order = jnp.argsort(gid)
        over = jnp.zeros((), jnp.int32)
        if pair_bound is not None and pair_bound < N * k:
            order = order[:pair_bound]
            ends = jnp.minimum(jnp.cumsum(sizes), pair_bound)
            kept = jnp.diff(ends, prepend=0)
            over = jnp.sum(sizes - kept)
            sizes = kept
    with jax.named_scope("experts"):
        xs = jnp.take(h, order // k, axis=0)
        up = grouped_matmul(xs, lp["up_w"], sizes, first, transpose_rhs=True,
                            row_tile=row_tile)
        if "gate_w" in lp:
            gate = grouped_matmul(xs, lp["gate_w"], sizes, first,
                                  transpose_rhs=True, row_tile=row_tile)
            act = swiglu(gate, up).astype(h.dtype)
        else:
            act = relu2(up).astype(h.dtype)
        down = grouped_matmul(act, lp["down_w"], sizes, first,
                              row_tile=row_tile)
        held = here.reshape(-1)[order]
        part = jnp.where(held[:, None], down.astype(jnp.float32) *
                         w.reshape(-1)[order][:, None], 0.0)
        routed = jnp.zeros((N, D), jnp.float32).at[order // k].add(part)
    with jax.named_scope("shared_expert"):
        shared_up = jnp.matmul(h, lp["shared_up_w"])
        shared_act = swiglu(jnp.matmul(h, lp["shared_gate_w"]), shared_up) \
            if "shared_gate_w" in lp else relu2(shared_up)
        shared = jnp.matmul(shared_act.astype(h.dtype), lp["shared_down_w"])
    load = sizes[first:first + E]
    counters = {
        "moe_pairs_here": jnp.sum(here, dtype=jnp.int32),
        "moe_pairs_away": jnp.sum(away, dtype=jnp.int32),
        "moe_experts_touched": jnp.sum(load > 0, dtype=jnp.int32),
        "moe_load_max": jnp.max(load).astype(jnp.int32),
    }
    if pair_bound is not None:
        chosen = (idx[..., None] == jnp.arange(E_all)) & real[:, None, None]
        counters.update(
            moe_load_min=jnp.min(load).astype(jnp.int32),
            moe_pairs_over_bound=over,
            load=jnp.sum(chosen, axis=(0, 1), dtype=jnp.int32))
    return (routed + shared.astype(jnp.float32)).astype(h.dtype), counters
