"""Paged attention for TPU serving (ref vLLM PagedAttention, Kwon et al.
SOSP 2023; reference repo counterpart: the fused variable-length attention used
by `fluid/inference` / PaddleNLP generation predictors).

The serving engine stores KV in a static pool of fixed-size pages
(`[num_pages, page_size, KVH, hd]` per layer) plus a per-slot page table, so
cache memory scales with live tokens instead of `B * max_seq_len`.  Attention
then has to read each slot's keys/values *through* the page table.  One
contract covers every lane of the serving step: a chunk of T query tokens per
slot (`q [B, T, H, hd]`), query t at absolute position `q_offset[b] + t`,
attends causally (`kv_pos <= q_offset + t`) to everything the table holds at
or below it — positions below the offset are what was written before (cached
prefix pages, earlier chunks, earlier decode steps), positions inside the
chunk mask causally.  `valid[b]` counts the chunk's real rows; rows past it
are padding whose output the caller ignores (their KV went to the null page
0).  The slot's mode is in those two numbers and its table row: plain decode
is `valid = 1` with `q_offset` = tokens cached, speculative verify (Leviathan
et al. 2023) `valid = 1 + K`, a chunk of a prompt (Sarathi-Serve, Agrawal et
al. OSDI 2024) `valid` = its tokens, an inactive slot a null row with
`valid = 0`.  That is what lets the engine dispatch ONE program a step
(`models.gpt.serve_step_paged`).

- `paged_prefill_attention`: the entry the models call.  The Pallas kernel on
  a TPU when the layout suits it (`_shapes_ok_for_pallas`), the oracle
  otherwise; head-sharded over `mesh`'s 'mp' axis when it has one.
- `paged_prefill_attention_xla`: gather-based (`pool[page_table]`) — the
  CPU/debug fallback and the numerics oracle for tests.  Fine at test scale,
  bandwidth-wasteful at pool scale because the gathered `[B, S_max, KVH, hd]`
  copy round-trips HBM.
- `paged_prefill_attention_pallas`: the kernel.  One grid step per (slot,
  query tile), and inside it a loop over the slot's LIVE pages only, in
  blocks of several pages.  The pools stay in HBM; the kernel starts one
  asynchronous copy a page, addressed through the scalar-prefetched table,
  into a double-buffered VMEM scratch, block i + 1 in flight while block i is
  weighed, and does one online-softmax update a block.  The trip count comes
  from `q_offset` and `valid`, so a dead table entry costs nothing - no grid
  step, no copy, no compute - and an inactive slot a few scalar
  instructions.  Pages a block and kv heads a score tile are functions of
  the shapes (`_pages_per_block`, `_heads_per_tile`).  GQA folds in as
  G = H // KVH query rows per kv head.
- `paged_prefill_attention_mp`: multi-chip serving.  Shards q on its head
  axis and the pool on KVH and runs the unmodified kernel per shard
  (shard_map), or the oracle under sharding constraints.  See the block
  comment above `_POOL_SPEC`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from . import _on_tpu, _out_struct
from .flash_attention import NEG_INF

# Tensor-parallel serving (multi-chip): attention is embarrassingly parallel
# over heads — no cross-head reduction anywhere in the softmax/PV chain — so
# the mp distribution is "each chip owns H/mp query heads and KVH/mp kv heads
# of EVERY page".  The page pool shards on its KVH axis, q on its head axis,
# and the page table / lengths / q_offset / valid scalars stay replicated
# (they are host-side scheduler state, identical on every chip).  Two routes:
# - Pallas (TPU): the kernel is grid-per-shard — `jax.shard_map` runs the
#   UNMODIFIED kernel on the local head slice of the pool.
# - XLA oracle (CPU / kernel-unfriendly layouts): sharding constraints pin the
#   head layout and GSPMD partitions the gather+einsum (the gather indexes the
#   pool's page axis, which is unsharded, so it stays collective-free).
_POOL_SPEC = P(None, None, "mp", None)      # [num_pages, page, KVH, hd]
# quantized-pool scale lanes [num_pages, page, KVH]: per-token-per-head f32
# scales shard on the SAME KVH axis as the int8 pages they dequantize
_SCALE_SPEC = P(None, None, "mp")


def _mp_degree(mesh) -> int:
    if mesh is None:
        return 1
    return int(dict(mesh.shape).get("mp", 1))


def _check_mp_heads(q_heads: int, kv_heads: int, mp: int) -> None:
    if q_heads % mp or kv_heads % mp:
        raise ValueError(
            f"tensor-parallel serving needs num_heads ({q_heads}) and "
            f"kv_heads ({kv_heads}) divisible by mp={mp}")


def _head_spec(ndim: int) -> P:
    """Shard the second-to-last ([..., H, hd]) axis over mp."""
    return P(*([None] * (ndim - 2)), "mp", None)


def _pin(mesh, x, spec):
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def paged_prefill_attention_mp(q, k_pages, v_pages, page_table, q_offset,
                               valid, mesh, scale=None, use_pallas=None,
                               interpret=False, kv_scales=None):
    """Head-sharded `paged_prefill_attention` over the `mp` axis of `mesh`.

    use_pallas=None auto-selects (TPU + kernel-friendly layout); tests force
    True with interpret=True to run the shard_mapped kernel on CPU.
    kv_scales (int8 pool) shard on the same KVH axis as the pages — the
    dequant is per-head-local, so the mp distribution is unchanged."""

    mp = _mp_degree(mesh)
    _check_mp_heads(q.shape[2], k_pages.shape[2], mp)
    if use_pallas is None:
        use_pallas = _on_tpu() and _shapes_ok_for_pallas(
            q, k_pages, quantized=kv_scales is not None)
    if use_pallas:
        if kv_scales is not None:
            def local_q(tbl, qo, vl, q_l, k_l, v_l, ks_l, vs_l):
                return paged_prefill_attention_pallas(
                    q_l, k_l, v_l, tbl, qo, vl, scale=scale,
                    interpret=interpret, kv_scales=(ks_l, vs_l))
            return jax.shard_map(
                local_q, mesh=mesh, axis_names={"mp"},
                in_specs=(P(None, None), P(None), P(None), _head_spec(4),
                          _POOL_SPEC, _POOL_SPEC, _SCALE_SPEC, _SCALE_SPEC),
                out_specs=_head_spec(4))(page_table, q_offset, valid, q,
                                         k_pages, v_pages, *kv_scales)

        def local(tbl, qo, vl, q_l, k_l, v_l):
            return paged_prefill_attention_pallas(q_l, k_l, v_l, tbl, qo, vl,
                                                  scale=scale,
                                                  interpret=interpret)
        return jax.shard_map(
            local, mesh=mesh, axis_names={"mp"},
            in_specs=(P(None, None), P(None), P(None), _head_spec(4),
                      _POOL_SPEC, _POOL_SPEC),
            out_specs=_head_spec(4))(page_table, q_offset, valid, q, k_pages,
                                     v_pages)
    q = _pin(mesh, q, _head_spec(4))
    k_pages = _pin(mesh, k_pages, _POOL_SPEC)
    v_pages = _pin(mesh, v_pages, _POOL_SPEC)
    if kv_scales is not None:
        kv_scales = (_pin(mesh, kv_scales[0], _SCALE_SPEC),
                     _pin(mesh, kv_scales[1], _SCALE_SPEC))
    out = paged_prefill_attention_xla(q, k_pages, v_pages, page_table,
                                      q_offset, valid, scale=scale,
                                      kv_scales=kv_scales)
    return _pin(mesh, out, _head_spec(4))


def _dequant_gathered(pages, scales, page_table, B, S, KVH, hd):
    """Gather int8 pages through the table and dequantize by their per-token
    scales (float32) — the oracle twin of the kernel's dequant on read."""
    x = pages[page_table].reshape(B, S, KVH, hd).astype(jnp.float32)
    s = scales[page_table].reshape(B, S, KVH)
    return x * s[..., None]


def paged_prefill_attention_xla(q, k_pages, v_pages, page_table, q_offset,
                                valid, scale=None, kv_scales=None):
    """Gather-based paged attention (fallback + oracle).

    q: [B, T, H, hd] — a chunk of T query tokens per slot; query t sits at
        absolute position q_offset[b] + t.
    k_pages/v_pages: [P, page_size, KVH, hd] — a page pool (the model's
        paged passes hand in all layers' pages as one [L*P, ...] pool and
        a page table offset to the layer's rows).
    page_table: [B, max_pages] int32 page ids (0 = reserved null page).
    q_offset: [B] int32 — absolute position of q[:, 0] (prefix already
        written below it: cached pages or earlier chunks).
    valid: [B] int32 — real tokens in the chunk; rows t >= valid[b] compute
        garbage the caller ignores (their KV was routed to the null page).
    kv_scales: (k_scale, v_scale) [P, page_size, KVH] float32 for an int8
        pool — per-token dequant on read, same math as the Pallas kernel.
    Returns [B, T, H, hd].
    """
    B, T, H, hd = q.shape
    page = k_pages.shape[1]
    KVH = k_pages.shape[2]
    G = H // KVH
    S = page_table.shape[1] * page
    s = scale if scale is not None else 1.0 / math.sqrt(hd)
    if kv_scales is not None:
        k = _dequant_gathered(k_pages, kv_scales[0], page_table, B, S, KVH, hd)
        v = _dequant_gathered(v_pages, kv_scales[1], page_table, B, S, KVH, hd)
    else:
        k = k_pages[page_table].reshape(B, S, KVH, hd)
        v = v_pages[page_table].reshape(B, S, KVH, hd)
    qg = q.reshape(B, T, KVH, G, hd)
    logits = jnp.einsum("btkgd,bskd->bkgts", qg, k,
                        preferred_element_type=jnp.float32) * s
    qpos = q_offset[:, None] + jnp.arange(T)                    # [B, T]
    mask = jnp.arange(S)[None, None] <= qpos[:, :, None]        # [B, T, S]
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgts,bskd->bkgtd", p.astype(v.dtype), v)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, hd)


# Query rows (heads x tokens) one grid step of the prefill kernel keeps in
# VMEM.  The q/o blocks, the f32 accumulator and the score tile all scale with
# it; 2048 rows of hd=128 is ~6 MiB of the 16 MiB a v5e core has, where the
# whole-T layout asked for 24 MiB at T=1024 (and 17.6 MiB at T=256).
_MAX_Q_ROWS = 2048
# What the prefill kernel may hold in VMEM in all (of a v5e core's 16 MiB
# scoped limit; the rest is left to Mosaic's own temporaries), and what one
# block of the walk aims at: about 1 MiB of K and V and at most 256 keys
# (measured on the v5e: 64 KB pages are fastest 8 to a block, 32 KB and 8 KB
# pages 16 to a block; a longer block only delays the first compute behind a
# longer cold copy).
_VMEM_BUDGET = 11 << 20
_BLOCK_BYTES = 1 << 20
_MAX_BLOCK_KEYS = 256
# A query tile of at most this many rows scores its block against ALL kv
# heads' keys at once (see `_heads_per_tile`).
_ALL_HEADS_MAX_ROWS = 256


def _heads_per_tile(KVH: int, rows: int) -> int:
    """KV heads whose keys share one score tile: all of them while the query
    tile is short, one otherwise.  A block arrives as [ppb, page, KVH, hd];
    all heads together it IS a [keys * KVH, hd] slab (key-major, head-minor)
    the MXU takes as it lies, at the price of scoring every row against every
    head's keys and masking the other heads' columns out.  The MXU streams a
    short tile's rows through each 128-key slab in the time it takes to load
    the slab, whoever's keys it holds, so up to a few hundred rows (every
    T = 1 and speculation step) that price is nil and the per-head gather of
    key rows out of the page layout - what bounded the kernel at KVH = 2 - is
    saved; a prefill-sized tile would pay KVH times its matmuls, so it
    gathers."""
    return KVH if rows <= _ALL_HEADS_MAX_ROWS else 1


def _pages_per_block(page: int, KVH: int, hd: int, itemsize: int, rows: int,
                     n_pages: int, quantized: bool = False) -> int:
    """Pages one block of the prefill kernel's walk fetches and weighs
    together - a function of the shapes alone.  What scales with the block:
    the double buffers of K and V, the block's K and V as values in the
    compute dtype, and the float32 score tile [rows, ppb * page * heads-per-tile] with its exp and mask beside it.
    What does not: the query/output tiles (double-buffered by the pipeline)
    and the acc / m / l carry, all set by `rows`, and an int8 pool's scales."""
    page_bytes = page * KVH * hd * itemsize
    fixed = rows * (4 * hd * itemsize + 2 * hd * 4 + 2 * 128 * 4)
    per_page = 4 * page_bytes + \
        2 * page * KVH * hd * (4 if quantized else itemsize) + \
        3 * rows * page * _heads_per_tile(KVH, rows) * 4
    if quantized:
        # the slot's gathered scale lanes, both double-buffered by the
        # pipeline: [n_pages, page, KVH] f32 with KVH padded to the lanes
        fixed += 4 * n_pages * page * 128 * 4
    fit = (_VMEM_BUDGET - fixed) // per_page
    return int(max(1, min(fit, _BLOCK_BYTES // (2 * page_bytes),
                          _MAX_BLOCK_KEYS // page, n_pages)))


def _paged_prefill_kernel(tbl_ref, qoff_ref, val_ref, q_ref, k_hbm, v_hbm,
                          *refs, page: int, KVH: int, G: int, bt: int,
                          ppb: int, hg: int, scale: float,
                          quantized: bool = False):
    """Grid (B, T/bt), both parallel: one grid step per slot and query tile
    of bt*H rows (kh-major stacking; VMEM use is set by the tile, not by
    T).  The pools stay in HBM.  Inside
    the step a `fori_loop` walks the slot's LIVE pages only — those at or
    below the tile's highest real query position — in blocks of `ppb` pages:
    block i+1's page copies (one `make_async_copy` a live page, addressed
    through the scalar-prefetched table) are started into the other half of a
    double-buffered VMEM scratch before block i is weighed, and each block is
    ONE online-softmax update over its ppb*page keys.  A dead table entry
    costs nothing: no grid step, no copy, no compute.  Inside the last block
    the pages past the slot's last live one are not copied; whatever the
    buffer holds there is masked out of the scores (`kv_pos <= q_offset + t`,
    clamped to the last real query so padding rows read nothing either) and
    zeroed out of V, so no stale or uninitialised value is ever weighed.
    Block 0 holds kv position 0, which every real row attends, so the running
    max is finite before any fully-masked row/block combination.  A tile of
    padding rows (or an inactive slot: `valid` 0) has a trip count of 0: no
    copy starts, and it writes zeros.  `quantized` adds the slot's two scale
    lanes, gathered through its table by the caller ([1, entries, page, KVH]
    blocks: Mosaic cannot address a copy into an HBM array whose minor
    dimension is narrower than the lanes): the int8 block dequantizes to f32
    on read, the oracle's math."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if quantized:
        ks_ref, vs_ref, *refs = refs
    o_ref, k_buf, v_buf, sem, acc_ref, m_ref, l_ref = refs
    lanes = ((k_hbm, k_buf), (v_hbm, v_buf))
    b = pl.program_id(0)
    ti = pl.program_id(1)
    R = KVH * bt * G
    keys = ppb * page

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    qoff = qoff_ref[b]
    t0 = ti * bt                        # first chunk row of this tile
    n_real = jnp.minimum(val_ref[b] - t0, bt)   # real rows in the tile
    last_q = qoff + t0 + n_real - 1     # highest real query position
    n_live = jnp.where(n_real > 0, last_q // page + 1, 0)   # pages to walk
    n_blocks = (n_live + ppb - 1) // ppb

    def each_live_page(i, half, act):
        """`act` on the copies of every live page of block i (none of a
        block past the walk's end): K and V of table entry i * ppb + p into
        row p of the buffers' `half`."""
        def one(p, carry):
            pid = tbl_ref[b, i * ppb + p]
            for n, (src, buf) in enumerate(lanes):
                act(pltpu.make_async_copy(src.at[pid], buf.at[half, p],
                                          sem.at[n, half]))
            return carry
        jax.lax.fori_loop(0, jnp.minimum(n_live - i * ppb, ppb), one, 0)

    def start(i, half):
        each_live_page(i, half, lambda cp: cp.start())

    def wait(i, half):
        each_live_page(i, half, lambda cp: cp.wait())

    start(0, 0)

    def block(i, carry):
        half = i % 2
        start(i + 1, 1 - half)
        wait(i, half)
        q = q_ref[0]                                    # [bt, H, hd]
        k = k_buf[half]                                 # [ppb, page, KVH, hd]
        v = v_buf[half]
        if quantized:
            at = pl.ds(i * ppb, ppb)
            k = k.astype(jnp.float32) * ks_ref[0, at][..., None]
            v = v.astype(jnp.float32) * vs_ref[0, at][..., None]
        k_start = i * keys
        # a page not copied holds anything, NaN included: 0 * NaN is NaN
        at_page = (ppb, page, 1, 1)
        row = k_start + page * jax.lax.broadcasted_iota(
            jnp.int32, at_page, 0) + jax.lax.broadcasted_iota(
            jnp.int32, at_page, 1)
        v = jnp.where(row <= last_q, v, jnp.zeros_like(v))
        # heads in groups of hg: the group's keys as one [keys * hg, hd]
        # slab (key-major, head-minor), its rows against all of it, and the
        # columns of another head than the row's masked out
        n_g = KVH // hg
        rg = bt * G * hg                                # rows of a group
        cols = keys * hg
        rows = []
        for g0 in range(n_g):
            qh = [q[:, kh * G:(kh + 1) * G, :].reshape(bt * G, -1)
                  for kh in range(g0 * hg, (g0 + 1) * hg)]
            qh = jnp.concatenate(qh, axis=0) if hg > 1 else qh[0]
            kk = (k if n_g == 1 else k[:, :, g0 * hg:(g0 + 1) * hg, :]
                  ).reshape(cols, -1)
            rows.append(jnp.dot(qh, kk.T,
                                preferred_element_type=jnp.float32))
        s = (jnp.concatenate(rows, axis=0) if n_g > 1 else rows[0]) * scale
        col = jax.lax.broadcasted_iota(jnp.int32, (R, cols), 1)
        rr = jax.lax.broadcasted_iota(jnp.int32, (R, cols), 0) % rg
        kv_pos = k_start + col // hg
        t_row = t0 + (rr % (bt * G)) // G
        ok = kv_pos <= jnp.minimum(qoff + t_row, last_q)
        if hg > 1:
            ok = ok & (col % hg == rr // (bt * G))
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                          # [R, cols]
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        upd = []
        for g0 in range(n_g):
            ph = p[g0 * rg:(g0 + 1) * rg].astype(v.dtype)
            vv = (v if n_g == 1 else v[:, :, g0 * hg:(g0 + 1) * hg, :]
                  ).reshape(cols, -1)
            upd.append(jnp.dot(ph, vv, preferred_element_type=jnp.float32))
        pv = jnp.concatenate(upd, axis=0) if n_g > 1 else upd[0]   # [R, hd]
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)

    l = jnp.maximum(l_ref[...], 1e-30)
    out = acc_ref[...] / l                              # [KVH*bt*G, hd]
    for kh in range(KVH):
        blk = out[kh * bt * G:(kh + 1) * bt * G].reshape(bt, G, -1)
        o_ref[0, :, kh * G:(kh + 1) * G, :] = blk.astype(o_ref.dtype)


def paged_prefill_attention_pallas(q, k_pages, v_pages, page_table, q_offset,
                                   valid, scale=None, interpret=False,
                                   kv_scales=None):
    """Pallas paged attention — same contract as
    `paged_prefill_attention_xla`.  page_table / q_offset / valid ride
    `PrefetchScalarGridSpec` and are all the walk needs: the trip count of a
    slot's loop and the address of every page copy come from them.  The T
    query tokens are tiled over a grid axis (`_MAX_Q_ROWS`), T padded up to a
    whole number of tiles; the pools are handed over in HBM and read in
    place, `_pages_per_block` pages a block; an int8 pool's `kv_scales` (1/32
    of its bytes at hd 128) are gathered through the table here and arrive a
    slot a block; `interpret=True` runs on CPU for numerics tests."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, hd = q.shape
    page = k_pages.shape[1]
    KVH = k_pages.shape[2]
    G = H // KVH
    n_pages = page_table.shape[1]
    s = scale if scale is not None else 1.0 / math.sqrt(hd)
    bt = min(T, max(1, _MAX_Q_ROWS // H))
    n_t = pl.cdiv(T, bt)
    if n_t * bt != T:
        # padded rows sit past `valid` (valid <= T): masked like any pad row
        q = jnp.pad(q, ((0, 0), (0, n_t * bt - T), (0, 0), (0, 0)))
    quantized = kv_scales is not None
    ppb = _pages_per_block(page, KVH, hd, k_pages.dtype.itemsize, bt * H,
                           n_pages, quantized)

    kernel = functools.partial(_paged_prefill_kernel, page=page, KVH=KVH,
                               G=G, bt=bt, ppb=ppb, hg=_heads_per_tile(
                                   KVH, bt * H), scale=s,
                               quantized=quantized)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    q_spec = pl.BlockSpec((1, bt, H, hd),
                          lambda b, t, tbl, qo, vl: (b, t, 0, 0))
    in_specs = [q_spec, hbm, hbm]
    args = [q, k_pages, v_pages]
    scratch = [pltpu.VMEM((2, ppb, page, KVH, hd), k_pages.dtype),
               pltpu.VMEM((2, ppb, page, KVH, hd), v_pages.dtype)]
    if quantized:
        # entries padded to whole blocks, so the last block's slice is inside
        wide = pl.cdiv(n_pages, ppb) * ppb
        tbl = jnp.pad(jnp.asarray(page_table, jnp.int32),
                      ((0, 0), (0, wide - n_pages)))
        sc_spec = pl.BlockSpec((1, wide, page, KVH),
                               lambda b, t, tbl, qo, vl: (b, 0, 0, 0))
        in_specs += [sc_spec, sc_spec]
        args += [kv_scales[0][tbl], kv_scales[1][tbl]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # (page_table, q_offset, valid)
        grid=(B, n_t),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=scratch + [
            pltpu.SemaphoreType.DMA((2, 2)),            # K or V x half
            pltpu.VMEM((KVH * bt * G, hd), jnp.float32),
            pltpu.VMEM((KVH * bt * G, 1), jnp.float32),
            pltpu.VMEM((KVH * bt * G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="paged_prefill",
        grid_spec=grid_spec,
        out_shape=_out_struct((B, n_t * bt, H, hd), q.dtype, q, k_pages,
                              v_pages),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(jnp.asarray(page_table, jnp.int32), jnp.asarray(q_offset, jnp.int32),
      jnp.asarray(valid, jnp.int32), *args)
    return out[:, :T] if n_t * bt != T else out


def paged_prefill_attention(q, k_pages, v_pages, page_table, q_offset, valid,
                            scale=None, mesh=None, kv_scales=None):
    """The serving passes' attention (`models.gpt._paged_chunk_hidden`,
    `models.hybrid`; contract in the module docstring): Pallas on TPU when
    the layout is kernel-friendly, gather fallback otherwise.  mesh (with an
    'mp' axis > 1) runs head-sharded tensor-parallel.  kv_scales (int8 pool)
    selects the dequant-on-read lane in every route."""
    if _mp_degree(mesh) > 1:
        return paged_prefill_attention_mp(q, k_pages, v_pages, page_table,
                                          q_offset, valid, mesh, scale=scale,
                                          kv_scales=kv_scales)
    if _on_tpu() and _shapes_ok_for_pallas(q, k_pages,
                                           quantized=kv_scales is not None):
        return paged_prefill_attention_pallas(q, k_pages, v_pages, page_table,
                                              q_offset, valid, scale=scale,
                                              kv_scales=kv_scales)
    return paged_prefill_attention_xla(q, k_pages, v_pages, page_table,
                                       q_offset, valid, scale=scale,
                                       kv_scales=kv_scales)


def _shapes_ok_for_pallas(q, k_pages, quantized=False):
    hd = q.shape[-1]
    page = k_pages.shape[1]
    ok = hd in (64, 128, 256) and page % 8 == 0
    if quantized:
        # int8 VMEM tiles are (32, 128) (pallas guide): keep the auto-route
        # to the kernel conservative on quantized pools — hd a full lane
        # width and whole-sublane pages — until the int8 layout is validated
        # on real hardware; anything else takes the XLA dequant-gather path
        ok = ok and hd in (128, 256) and page % 32 == 0
    return ok


# ---------------------------------------------------------------------------
# Latent (MLA) pages: absorbed attention over ONE lane
# ---------------------------------------------------------------------------
# A latent-attention layer (DeepSeek-V2, arXiv:2405.04434 section 2.1) caches
# per token one row [c_kv | k_r | 0..]: the normed latent (`latent` numbers),
# the one rotated key all heads share, and zeros up to a whole number of
# 128-lane tiles (W; the chip pads an HBM array's minor dimension to that
# anyway, and Mosaic refuses to address a row it would have to find inside
# the padding, so the lane states its real width).  In the absorbed
# form every query head carries q_lat = q_nope W^K (latent wide) beside its
# rotated part, scores against the cached row itself, and weighs the row's
# first `latent` columns as its values: the kv up-projection is never applied
# to the cache.  Same (`q_offset`, `valid`) contract as above, so decode,
# chunk and verify lanes stay one program.
#
# A sibling of `_paged_prefill_kernel`, not a mode of it.  What that kernel
# is built around does not exist here (two pools of one width copied in
# lock-step, kv heads stacked kh-major into the score tile and their
# neighbours' columns masked out, the per-head gather `hg` chooses against,
# the int8 scale lanes), and what this one needs does not exist there (the
# score is two products of different widths against column slices of ONE
# buffer, the values are a slice of the keys, the output is narrower than the
# query): as modes of one body each would sit behind a branch at every line
# of the block, in the kernel three accepted cells run.  The walk - live
# pages only, double-buffered blocks of page copies, one online-softmax
# update a block - is the same and reads the same.

# query rows (tokens x heads) a grid step keeps in VMEM, and keys a block
# holds at most: a decode step's 32 rows leave room for long blocks, which is
# what amortises a block's fixed cost against 1,280 B a key (PERF.md has the
# chip's readings)
_LATENT_MAX_Q_ROWS = 512
_LATENT_MAX_BLOCK_KEYS = 512


def _latent_pages_per_block(page: int, W: int, latent: int, itemsize: int,
                            rows: int, n_pages: int) -> int:
    """Pages a block of the latent kernel's walk holds: the double buffer,
    the block as a value and the float32 score tile with its exp and mask
    scale with it; the query/output tiles and the carry do not."""
    fixed = rows * (2 * W * itemsize + 2 * latent * itemsize +
                    latent * 4 + 2 * 128 * 4)
    per_page = 3 * page * W * itemsize + 3 * rows * page * 4
    fit = (_VMEM_BUDGET - fixed) // per_page
    return int(max(1, min(fit, _LATENT_MAX_BLOCK_KEYS // page, n_pages)))


def paged_latent_attention_xla(q, c_pages, page_table, q_offset, valid,
                               latent: int, scale: float):
    """Gather-based absorbed attention (fallback + oracle).

    q [B, T, H, W]: per head [q_lat | q_rope | 0..]; c_pages [P, page, W]:
    per token [c_kv | k_r | 0..]; page_table / q_offset / valid as in
    `paged_prefill_attention_xla`.  Returns [B, T, H, latent]: the
    probability-weighted latents, which the caller takes through W^V."""
    B, T, H, W = q.shape
    S = page_table.shape[1] * c_pages.shape[1]
    k = c_pages[page_table].reshape(B, S, W)
    logits = jnp.einsum("bthw,bsw->bhts", q, k,
                        preferred_element_type=jnp.float32) * scale
    qpos = q_offset[:, None] + jnp.arange(T)
    mask = jnp.arange(S)[None, None] <= qpos[:, :, None]        # [B, T, S]
    p = jax.nn.softmax(jnp.where(mask[:, None], logits, NEG_INF), axis=-1)
    return jnp.einsum("bhts,bsc->bthc", p.astype(k.dtype), k[..., :latent])


def _paged_latent_kernel(tbl_ref, qoff_ref, val_ref, q_ref, c_hbm, o_ref,
                         buf, sem, acc_ref, m_ref, l_ref, *, page: int,
                         H: int, bt: int, ppb: int, latent: int,
                         scale: float):
    """Grid (B, T/bt): a slot and a query tile of bt*H rows (token-major) a
    step; the walk is `_paged_prefill_kernel`'s (live pages only, block
    i + 1's copies in flight while block i is weighed, rows past the last
    real query's position zeroed and masked)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    ti = pl.program_id(1)
    R = bt * H
    keys = ppb * page

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    qoff = qoff_ref[b]
    t0 = ti * bt
    n_real = jnp.minimum(val_ref[b] - t0, bt)
    last_q = qoff + t0 + n_real - 1
    n_live = jnp.where(n_real > 0, last_q // page + 1, 0)
    n_blocks = (n_live + ppb - 1) // ppb

    def each_live_page(i, half, act):
        def one(p, carry):
            pid = tbl_ref[b, i * ppb + p]
            act(pltpu.make_async_copy(c_hbm.at[pid], buf.at[half, p],
                                      sem.at[half]))
            return carry
        jax.lax.fori_loop(0, jnp.minimum(n_live - i * ppb, ppb), one, 0)

    def start(i, half):
        each_live_page(i, half, lambda cp: cp.start())

    def wait(i, half):
        each_live_page(i, half, lambda cp: cp.wait())

    start(0, 0)

    def block(i, carry):
        half = i % 2
        start(i + 1, 1 - half)
        wait(i, half)
        q = q_ref[0].reshape(R, -1)                     # [R, W]
        k = buf[half].reshape(keys, -1)                 # [keys, W]
        k_start = i * keys
        # a page not copied holds anything, NaN included: 0 * NaN is NaN
        row = k_start + jax.lax.broadcasted_iota(jnp.int32, (keys, 1), 0)
        k = jnp.where(row <= last_q, k, jnp.zeros_like(k))
        c = k[:, :latent]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        kv_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (R, keys), 1)
        t_row = t0 + jax.lax.broadcasted_iota(jnp.int32, (R, keys), 0) // H
        s = jnp.where(kv_pos <= jnp.minimum(qoff + t_row, last_q), s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(c.dtype), c, preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)

    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = out.reshape(bt, H, latent).astype(o_ref.dtype)


def paged_latent_attention_pallas(q, c_pages, page_table, q_offset, valid,
                                  latent: int, scale: float,
                                  interpret=False):
    """The kernel behind `paged_latent_attention` (same contract as the
    oracle)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, W = q.shape
    page = c_pages.shape[1]
    n_pages = page_table.shape[1]
    bt = min(T, max(1, _LATENT_MAX_Q_ROWS // H))
    n_t = pl.cdiv(T, bt)
    if n_t * bt != T:
        q = jnp.pad(q, ((0, 0), (0, n_t * bt - T), (0, 0), (0, 0)))
    ppb = _latent_pages_per_block(page, W, latent, c_pages.dtype.itemsize,
                                  bt * H, n_pages)
    kernel = functools.partial(_paged_latent_kernel, page=page, H=H, bt=bt,
                               ppb=ppb, latent=latent, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # (page_table, q_offset, valid)
        grid=(B, n_t),
        in_specs=[pl.BlockSpec((1, bt, H, W),
                               lambda b, t, tbl, qo, vl: (b, t, 0, 0)),
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec((1, bt, H, latent),
                               lambda b, t, tbl, qo, vl: (b, t, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page, W), c_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((bt * H, latent), jnp.float32),
            pltpu.VMEM((bt * H, 1), jnp.float32),
            pltpu.VMEM((bt * H, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="paged_latent",
        grid_spec=grid_spec,
        out_shape=_out_struct((B, n_t * bt, H, latent), q.dtype, q, c_pages),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(jnp.asarray(page_table, jnp.int32), jnp.asarray(q_offset, jnp.int32),
      jnp.asarray(valid, jnp.int32), q, c_pages)
    return out[:, :T] if n_t * bt != T else out


def paged_latent_attention(q, c_pages, page_table, q_offset, valid,
                           latent: int, scale: float):
    """The latent layers' attention in both serving passes
    (`models.hybrid`): the kernel on a TPU where the layout suits it (whole
    128-lane latents, whole-sublane pages), the oracle otherwise."""
    if _on_tpu() and latent % 128 == 0 and q.shape[-1] % 128 == 0 \
            and c_pages.shape[1] % 16 == 0 and q.shape[2] % 8 == 0:
        return paged_latent_attention_pallas(q, c_pages, page_table, q_offset,
                                             valid, latent, scale)
    return paged_latent_attention_xla(q, c_pages, page_table, q_offset, valid,
                                      latent, scale)
