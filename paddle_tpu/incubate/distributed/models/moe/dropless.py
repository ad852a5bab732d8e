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

`pair_bound`: a fused serving step has a few tokens a slot and gathers all
N x k pairs, the absent experts' too.  A training step at N = 32,768 and
k = 8 would gather 262,144 rows of D to compute the sixteenth of them that
fell here; with `pair_bound` only the first `pair_bound` held pairs (in
expert order) are gathered and multiplied.  The bound is static, so it can be
exceeded: the pairs past it are left out AND counted
(`moe_pairs_over_bound`), and a caller that promises a dropless layer checks
that the count is nought.

`moe_dropless_compact` is the layer for a long serving pass (a prompt of
thousands of tokens): no array of N x k rows of width D or F.  The held pairs
get their places by counting (a running count a held expert, no sort), each
expert's group starting on a whole row tile of the grouped product, and go
through it in chunks of a static row count sized from the expected load; a
`lax.while_loop` runs as many chunks as the held pairs fill, so nothing is
dropped whatever the routing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ....kernels.grouped_matmul import ROW_TILE, TRAIN_ROW_TILE, \
    grouped_matmul

COUNTERS = ("moe_pairs_here", "moe_pairs_away", "moe_experts_touched",
            "moe_load_max")
# what a training step reads besides (`moe_dropless(..., pair_bound=...)`)
TRAIN_COUNTERS = COUNTERS + ("moe_load_min", "moe_pairs_over_bound")
# what the compact layer returns besides: the held pairs it computed, and
# the chunks it ran past the first (how often the static chunk was short)
COMPACT_COUNTERS = ("moe_pairs_compacted", "moe_compact_extra_chunks")
# a compact chunk's rows as a multiple of the held pairs a router with even
# loads sends here, before the groups' padding to whole row tiles
COMPACT_SLACK = 1.25


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


def expert_mlp(lp, xs, sizes, first: int, row_tile: int, dtype):
    """The held experts' product of rows xs [M, D] sorted by group (`sizes`
    over the router's experts): up (and gate), the activation, down."""
    up = grouped_matmul(xs, lp["up_w"], sizes, first, transpose_rhs=True,
                        row_tile=row_tile)
    if "gate_w" in lp:
        gate = grouped_matmul(xs, lp["gate_w"], sizes, first,
                              transpose_rhs=True, row_tile=row_tile)
        act = swiglu(gate, up).astype(dtype)
    else:
        act = relu2(up).astype(dtype)
    return grouped_matmul(act, lp["down_w"], sizes, first, row_tile=row_tile)


def shared_expert(lp, h):
    shared_up = jnp.matmul(h, lp["shared_up_w"])
    shared_act = swiglu(jnp.matmul(h, lp["shared_gate_w"]), shared_up) \
        if "shared_gate_w" in lp else relu2(shared_up)
    return jnp.matmul(shared_act.astype(h.dtype), lp["shared_down_w"])


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
        down = expert_mlp(lp, xs, sizes, first, row_tile, h.dtype)
        held = here.reshape(-1)[order]
        part = jnp.where(held[:, None], down.astype(jnp.float32) *
                         w.reshape(-1)[order][:, None], 0.0)
        routed = jnp.zeros((N, D), jnp.float32).at[order // k].add(part)
    with jax.named_scope("shared_expert"):
        shared = shared_expert(lp, h)
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


def compact_tiling(n_tokens: int, cfg) -> tuple:
    """(chunk rows, row tile) of `moe_dropless_compact` for n_tokens: the
    tile is the rows an expert expects (N k / E_total) in a power of two
    from `ROW_TILE` to `TRAIN_ROW_TILE` (a larger tile streams an expert's
    matrices fewer times, a smaller one computes fewer padding rows); the
    chunk `COMPACT_SLACK` times the held pairs of even loads plus half a
    tile a held expert, in whole tiles, and no more than all pairs held
    with every group padded can fill."""
    k, E_all, E = cfg.num_experts_per_tok, cfg.n_routed_experts, \
        cfg.experts_here
    tm = ROW_TILE
    while 2 * tm <= min(n_tokens * k // E_all, TRAIN_ROW_TILE):
        tm *= 2
    even = n_tokens * k * E / E_all
    most = n_tokens * min(k, E) + E * (tm - 1)
    rows = min(int(COMPACT_SLACK * even) + E * tm // 2, most)
    return -(-rows // tm) * tm, tm


def moe_dropless_compact(lp, h, cfg, real, chunk: int, row_tile: int):
    """`moe_dropless` over the held pairs only (the module docstring):
    the same routing, output and counters (`moe_load_max` this call's), and
    `COMPACT_COUNTERS`.  `chunk` (a multiple of `row_tile`) and `row_tile`
    as `compact_tiling` gives them."""
    N, D = h.shape
    k = cfg.num_experts_per_tok
    first, E = cfg.expert_offset, cfg.experts_here
    tm = row_tile
    i32, f32 = jnp.int32, jnp.float32
    with jax.named_scope("router"):
        idx, w = route(h, lp, cfg)
        held_id = (idx >= first) & (idx < first + E)
        here = held_id & real[:, None]
        away = ~held_id & real[:, None]
        # a held pair's place: its expert's group start (groups in expert
        # order, each padded to whole row tiles) + the held pairs of that
        # expert before it in token order
        mine = here.reshape(-1, 1) & \
            (idx.reshape(-1, 1) - first == jnp.arange(E, dtype=i32))
        count = jnp.cumsum(mine, axis=0, dtype=i32)          # [N k, E]
        load = count[-1]
        padded = -(-load // tm) * tm
        start = jnp.cumsum(padded) - padded
        place = jnp.sum(jnp.where(mine, count - 1 + start, 0), axis=1)
        # every pair held, every group padded: whole chunks
        cap = -(-(N * min(k, E) + E * (tm - 1)) // chunk) * chunk
        place = jnp.where(here.reshape(-1), place, cap)     # dropped below
        src = jnp.full((cap,), N, i32).at[place].set(
            jnp.arange(N * k, dtype=i32) // k, mode="drop")
        weight = jnp.zeros((cap,), f32).at[place].set(w.reshape(-1),
                                                      mode="drop")
        chunks = -(-jnp.sum(padded) // chunk)

    def one_chunk(carry):
        j, acc = carry
        lo = j * chunk
        rows = jax.lax.dynamic_slice_in_dim(src, lo, chunk)
        # each group's padded rows that fall in this chunk, in order
        ends = jnp.clip(start + padded - lo, 0, chunk)
        sizes = jnp.zeros((cfg.n_routed_experts,), i32).at[
            first:first + E].set(ends - jnp.clip(start - lo, 0, chunk))
        xs = jnp.take(h, jnp.minimum(rows, N - 1), axis=0)
        down = expert_mlp(lp, xs, sizes, first, tm, h.dtype)
        part = jnp.where((rows < N)[:, None], down.astype(f32) *
                         jax.lax.dynamic_slice_in_dim(weight, lo, chunk)[
                             :, None], 0.0)
        return j + 1, acc.at[rows].add(part, mode="drop")

    with jax.named_scope("experts"):
        _, routed = jax.lax.while_loop(
            lambda carry: carry[0] < chunks, one_chunk,
            (jnp.zeros((), i32), jnp.zeros((N, D), f32)))
    with jax.named_scope("shared_expert"):
        shared = shared_expert(lp, h)
    pairs = jnp.sum(here, dtype=i32)
    counters = {
        "moe_pairs_here": pairs,
        "moe_pairs_away": jnp.sum(away, dtype=i32),
        "moe_experts_touched": jnp.sum(load > 0, dtype=i32),
        "moe_load_max": jnp.max(load).astype(i32),
        "moe_pairs_compacted": pairs,
        "moe_compact_extra_chunks": jnp.maximum(chunks - 1, 0).astype(i32),
    }
    return (routed + shared.astype(f32)).astype(h.dtype), counters
