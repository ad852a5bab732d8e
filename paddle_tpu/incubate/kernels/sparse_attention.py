"""Learned sparse attention over paged latent rows (DeepSeek sparse
attention, DSA: DeepSeek-V3.2-Exp, as GLM-5 adopts it).

A DSA layer keeps a second paged lane beside the latent one, under the same
page table: per token its indexer key k^I (`index_head_dim` numbers, one key
for all indexer heads).  A query token t scores every earlier position s

    I[t, s] = sum_j w[t, j] * ReLU(q^I[t, j] . k^I[s])        (s <= t)

and its latent attention then runs over S_t, the top-min(t + 1, k) positions
by I (ties to the lower position), shared by all attention heads.  Three
pieces, each with an XLA oracle that is the CPU route and the numerics
reference of the tests:

- `paged_index_scores`: the indexer's walk over a slot's LIVE index pages
  (`paged_latent_attention`'s walk and its (`q_offset`, `valid`) contract),
  scores out in float32, -inf where s > t.  Scores are laid out by key block,
  [B, n_blocks, T, BLOCK_KEYS] (`blocked` / `unblocked`): the masked latent
  kernel reads them a block at a time at a leading index, and a decode row
  (T = 1) is one flat row as it lies.
- `topk_threshold`: the exact top-k of each row, from the scores, as the
  k-th largest score and the position of the last tie with it that is kept
  (ties to the lower position), found by counting bit by bit (no sort; the
  kernel `topk_threshold`); `mask_from` turns it into a mask.
- `topk_rows_decode` + `sparse_latent_attention`: the decode form - the k
  positions themselves, their latent rows gathered, and absorbed attention
  over those rows only (`sparse_latent`).  On a TPU the positions come by
  counting too (`topk_rows_pallas`): the slots' rows as the rows of one
  `topk_threshold` tile, then `topk_compact` lists each row's kept positions
  in ascending order; elsewhere `topk_rows` (`lax.top_k`, a sort: ties to
  the lower index) is the route and the oracle.

The masked form of the latent attention (every live row walked, the rows
outside S_t masked out) is `paged_attention.paged_latent_attention` with its
`select` argument, absorbed; a long pass takes it expanded, per head
(`masked_expanded_attention`: keys built once a layer, the selection as one
keep byte a token and key).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import _on_tpu, _out_struct
from .flash_attention import NEG_INF

# keys of one block of the indexer's walk and of the masked latent walk: the
# scores' layout is blocked by it, so both kernels walk the same blocks
BLOCK_KEYS = 512
# query rows (tokens x indexer heads) one grid step of the index walk keeps
_INDEX_MAX_Q_ROWS = 512


def block_pages(page: int) -> int:
    return max(1, BLOCK_KEYS // page)


def n_blocks(n_pages: int, page: int) -> int:
    return -(-n_pages // block_pages(page))


def blocked(x, page: int):
    """[B, T, S] (S = table entries x page) -> [B, n_blocks, T, keys], padded
    with -inf."""
    B, T, S = x.shape
    keys = block_pages(page) * page
    nb = -(-S // keys)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, nb * keys - S)),
                constant_values=NEG_INF if x.dtype == jnp.float32 else 0)
    return x.reshape(B, T, nb, keys).transpose(0, 2, 1, 3)


def unblocked(x):
    """[B, n_blocks, T, keys] -> [B, T, n_blocks * keys]."""
    B, nb, T, keys = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, T, nb * keys)


# ---------------------------------------------------------------------------
# the indexer's scores
# ---------------------------------------------------------------------------

def paged_index_scores_xla(q, w, ix_pages, page_table, q_offset, valid):
    """Gather-based index scores (fallback + oracle).

    q [B, T, Hi, Di]: the indexer's queries (rotated); w [B, T, Hi] float32:
    the head weights; ix_pages [P, page, Di]: per token k^I; page_table /
    q_offset / valid as in `paged_latent_attention`.  Returns float32
    [B, n_blocks, T, BLOCK_KEYS]: I[t, s] for s <= q_offset + t, -inf past
    it (and on padding rows)."""
    B, T, Hi, Di = q.shape
    page = ix_pages.shape[1]
    S = page_table.shape[1] * page
    k = ix_pages[page_table].reshape(B, S, Di)
    dots = jnp.einsum("bthd,bsd->bths", q, k,
                      preferred_element_type=jnp.float32)
    s = jnp.einsum("bth,bths->bts", w.astype(jnp.float32),
                   jnp.maximum(dots, 0.0))
    qpos = q_offset[:, None] + jnp.arange(T)
    ok = (jnp.arange(S)[None, None] <= qpos[:, :, None]) & \
        (jnp.arange(T)[None, :, None] < valid[:, None, None])
    return blocked(jnp.where(ok, s, NEG_INF), page)


def _index_kernel(tbl_ref, qoff_ref, val_ref, q_ref, w_ref, ix_hbm, o_ref,
                  buf, sem, *, page: int, Hi: int, bt: int, ppb: int):
    """Grid (B, T/bt): a slot and a tile of bt query tokens (bt * Hi rows,
    token-major) a step.  The walk is `_paged_latent_kernel`'s: the slot's
    live index pages only, ppb pages a block, block i + 1's copies in flight
    while block i is scored; block i's scores go to o_ref[0, i].  Blocks past
    the walk keep the -inf they start with."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    ti = pl.program_id(1)
    R = bt * Hi
    keys = ppb * page

    o_ref[...] = jnp.full_like(o_ref, NEG_INF)
    qoff = qoff_ref[b]
    t0 = ti * bt
    n_real = jnp.minimum(val_ref[b] - t0, bt)
    last_q = qoff + t0 + n_real - 1
    n_live = jnp.where(n_real > 0, last_q // page + 1, 0)
    nb = (n_live + ppb - 1) // ppb

    def each_live_page(i, half, act):
        def one(p, carry):
            pid = tbl_ref[b, i * ppb + p]
            act(pltpu.make_async_copy(ix_hbm.at[pid], buf.at[half, p],
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
        q = q_ref[0].reshape(R, -1)                     # [R, Di]
        k = buf[half].reshape(keys, -1)                 # [keys, Di]
        k_start = i * keys
        row = k_start + jax.lax.broadcasted_iota(jnp.int32, (keys, 1), 0)
        k = jnp.where(row <= last_q, k, jnp.zeros_like(k))
        s = jnp.maximum(jnp.dot(q, k.T, preferred_element_type=jnp.float32),
                        0.0)
        s = jnp.sum(s.reshape(bt, Hi, keys) * w_ref[0], axis=1)   # [bt, keys]
        kv_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bt, keys), 1)
        t_row = t0 + jax.lax.broadcasted_iota(jnp.int32, (bt, keys), 0)
        ok = (kv_pos <= qoff + t_row) & (t_row < t0 + n_real)
        o_ref[0, i] = jnp.where(ok, s, NEG_INF)
        return carry

    jax.lax.fori_loop(0, nb, block, 0)


def paged_index_scores_pallas(q, w, ix_pages, page_table, q_offset, valid,
                              interpret=False):
    """The kernel behind `paged_index_scores` (same contract as the
    oracle)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, Hi, Di = q.shape
    page = ix_pages.shape[1]
    n_pages = page_table.shape[1]
    ppb = block_pages(page)
    nb = n_blocks(n_pages, page)
    bt = min(T, max(1, _INDEX_MAX_Q_ROWS // Hi))
    n_t = pl.cdiv(T, bt)
    if n_t * bt != T:
        q = jnp.pad(q, ((0, 0), (0, n_t * bt - T), (0, 0), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, n_t * bt - T), (0, 0)))
    # the table padded to whole blocks, so that no copy is addressed past it
    tbl = jnp.pad(jnp.asarray(page_table, jnp.int32),
                  ((0, 0), (0, nb * ppb - n_pages)))
    kernel = functools.partial(_index_kernel, page=page, Hi=Hi, bt=bt,
                               ppb=ppb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # (page_table, q_offset, valid)
        grid=(B, n_t),
        in_specs=[pl.BlockSpec((1, bt, Hi, Di),
                               lambda b, t, tb, qo, vl: (b, t, 0, 0)),
                  pl.BlockSpec((1, bt, Hi, 1),
                               lambda b, t, tb, qo, vl: (b, t, 0, 0)),
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec((1, nb, bt, ppb * page),
                               lambda b, t, tb, qo, vl: (b, 0, t, 0)),
        scratch_shapes=[pltpu.VMEM((2, ppb, page, Di), ix_pages.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    out = pl.pallas_call(
        kernel,
        name="paged_index_scores",
        grid_spec=grid_spec,
        out_shape=_out_struct((B, nb, n_t * bt, ppb * page), jnp.float32, q,
                              ix_pages),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(tbl, jnp.asarray(q_offset, jnp.int32), jnp.asarray(valid, jnp.int32),
      q, w.astype(jnp.float32)[..., None], ix_pages)
    return out[:, :, :T] if n_t * bt != T else out


def _kernel_ok(page: int, Di: int, Hi: int) -> bool:
    return page % 16 == 0 and Di % 128 == 0 and Hi % 8 == 0


def paged_index_scores(q, w, ix_pages, page_table, q_offset, valid):
    """The indexer's scores in every serving pass (`models.hybrid`): the
    kernel on a TPU where the layout suits it, the oracle otherwise."""
    if _on_tpu() and _kernel_ok(ix_pages.shape[1], q.shape[-1], q.shape[2]):
        return paged_index_scores_pallas(q, w, ix_pages, page_table,
                                         q_offset, valid)
    return paged_index_scores_xla(q, w, ix_pages, page_table, q_offset,
                                  valid)


# ---------------------------------------------------------------------------
# exact top-k
# ---------------------------------------------------------------------------

_INT_MIN = -(1 << 31)
# query rows one grid step of the threshold search holds ([rows, n_blocks *
# keys] float32 in VMEM, double-buffered: 4 MiB a copy at 32,768 keys)
_THRESHOLD_ROWS = 16


def order_key(scores):
    """float32 -> int32 in the same order (-0 is 0)."""
    b = jax.lax.bitcast_convert_type(jnp.where(scores == 0, 0.0, scores),
                                     jnp.int32)
    return jnp.where(b < 0, b ^ 0x7FFFFFFF, b)


def _search(count, k, shape):
    """(thr, last): thr the k-th largest order key of a row, last the
    position of the last tie with it that the top-k keeps (ties to the lower
    position); `count(pred)` sums pred(key, pos) over a row's candidates.
    Bit by bit from the top: 32 counts for the value, 16 for the position.
    A row with at most k candidates gets thr = INT_MIN: all of them."""
    x = jnp.where(count(lambda key, pos: key >= 0) >= k, 0, _INT_MIN)
    x = jnp.broadcast_to(x, shape).astype(jnp.int32)
    for bit in range(30, -1, -1):
        cand = x | (1 << bit)
        x = jnp.where(count(lambda key, pos: key >= cand) >= k, cand, x)
    need = k - count(lambda key, pos: key > x)
    last = jnp.zeros(shape, jnp.int32)
    for bit in range(15, -1, -1):
        cand = last | (1 << bit)
        n = count(lambda key, pos: (key == x) & (pos < cand))
        last = jnp.where(n < need, cand, last)
    return x, last


def mask_from(scores, thr, last):
    """The top-k mask of blocked scores [B, nb, T, keys] from `topk_threshold`'s
    (thr, last) [B, T, 1]."""
    B, nb, T, keys = scores.shape
    key = order_key(scores)
    pos = (jnp.arange(nb)[:, None, None] * keys +
           jnp.arange(keys)[None, None, :])
    t, p = thr[:, None], last[:, None]
    return (scores > NEG_INF) & ((key > t) | ((key == t) & (pos <= p)))


def topk_threshold_xla(scores, k: int):
    """(thr, last) [B, T, 1] int32 of blocked scores [B, nb, T, keys] (-1e30:
    not a candidate): see `_search`."""
    B, nb, T, keys = scores.shape
    flat = unblocked(scores)                            # [B, T, S]
    key = order_key(flat)
    live = flat > NEG_INF
    pos = jnp.arange(flat.shape[-1])

    def count(pred):
        return jnp.sum(live & pred(key, pos), axis=-1, keepdims=True,
                       dtype=jnp.int32)
    return _search(count, k, (B, T, 1))


def _threshold_kernel(qoff_ref, s_ref, thr_ref, last_ref, key_ref, *, k: int,
                      bt: int, keys: int):
    """Grid (B, T/bt): bt rows of scores whole in VMEM, the 48 counts of
    `_search` over them.  The order keys are made once into a scratch
    (INT_MIN where a score is no candidate), a count sums per lane over the
    blocks that can hold a candidate of the tile (causal: none past its last
    row's position) and across lanes once at the end; a tile whose rows have
    at most k candidates each takes them all without a count."""
    from jax.experimental import pallas as pl

    nb = s_ref.shape[1]
    last_pos = qoff_ref[pl.program_id(0)] + pl.program_id(1) * bt + bt - 1
    n_live = jnp.minimum(last_pos // keys + 1, nb)

    def make(i, carry):
        sc = s_ref[0, i]                                  # [bt, keys]
        key_ref[i] = jnp.where(sc > NEG_INF, order_key(sc), _INT_MIN)
        return carry

    def count(pred):
        def body(i, acc):
            pos = i * keys + jax.lax.broadcasted_iota(jnp.int32,
                                                      (bt, keys), 1)
            return acc + pred(key_ref[i], pos).astype(jnp.int32)
        acc = jax.lax.fori_loop(0, n_live, body,
                                jnp.zeros((bt, keys), jnp.int32))
        return jnp.sum(acc, axis=-1, keepdims=True)

    @pl.when(last_pos < k)
    def _all():
        thr_ref[0] = jnp.full((bt, 1), _INT_MIN, jnp.int32)
        last_ref[0] = jnp.zeros((bt, 1), jnp.int32)

    @pl.when(last_pos >= k)
    def _search_tile():
        jax.lax.fori_loop(0, n_live, make, 0)
        thr, last = _search(count, k, (bt, 1))
        thr_ref[0] = thr
        last_ref[0] = last


def _threshold_rows(T: int) -> int:
    return next((b for b in (_THRESHOLD_ROWS, 8) if T % b == 0), T)


def topk_threshold_pallas(scores, k: int, q_offset=None, interpret=False):
    """`q_offset` [B] (the scores' first query's position; 0 if None) lets a
    tile skip the blocks past its last row."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, nb, T, keys = scores.shape
    bt = _threshold_rows(T)
    if q_offset is None:
        q_offset = jnp.zeros((B,), jnp.int32)
    row = pl.BlockSpec((1, bt, 1), lambda b, t, qo: (b, t, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,          # q_offset
        grid=(B, T // bt),
        in_specs=[pl.BlockSpec((1, nb, bt, keys),
                               lambda b, t, qo: (b, 0, t, 0))],
        out_specs=[row, row],
        scratch_shapes=[pltpu.VMEM((nb, bt, keys), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_threshold_kernel, k=k, bt=bt, keys=keys),
        name="topk_threshold",
        grid_spec=grid_spec,
        out_shape=[_out_struct((B, T, 1), jnp.int32, scores)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(jnp.asarray(q_offset, jnp.int32), scores)


def topk_threshold(scores, k: int, q_offset=None):
    """The exact top-k of every row of blocked scores, as (thr, last): what
    the masked passes read (`mask_from`).  No sort: counting by bits."""
    T = scores.shape[2]
    if _on_tpu() and (_threshold_rows(T) % 8 == 0 or T < 8) and \
            scores.shape[-1] % 128 == 0:
        return topk_threshold_pallas(scores, k, q_offset)
    return topk_threshold_xla(scores, k)


def topk_rows(scores, k: int):
    """(positions [B, T, k'] of the top-k' of each row, ties to the lower
    position; how many of them are candidates [B, T]), k' = min(k, row
    width), from blocked scores.  Candidates come first (`lax.top_k` sorts
    by score); the rest are position 0 and are masked by the count."""
    flat = unblocked(scores)
    flat = jnp.where(flat == 0, 0.0, flat)          # -0 ties with 0
    vals, idx = jax.lax.top_k(flat, min(k, flat.shape[-1]))
    ok = vals > NEG_INF
    return jnp.where(ok, idx, 0), jnp.sum(ok, axis=-1).astype(jnp.int32)


def _compact_kernel(nlive_ref, s_ref, thr_ref, last_ref, idx_ref, n_ref, *,
                    keys: int):
    """Grid (rows / bt,): bt decode rows' scores [nb, bt, keys] whole in
    VMEM; the positions `mask_from`'s rule keeps (thr, last [bt, 1]) written
    in ascending order into idx_ref [tiles, bt, 128] (tile j // 128 holds
    output j of a row), their count into n_ref.  128 positions of a row at a
    time: each kept lane moves down by the count of lanes before it that are
    not kept (one shift a bit of that count, low bit first, so that no two
    collide), the chunk is turned by the row's running count mod 128 and
    lands in that count's output tile and the next.  No sort, no scatter."""
    from jax.experimental.pallas import tpu as pltpu

    tiles, bt, _ = idx_ref.shape
    thr, last = thr_ref[0], last_ref[0]                   # [bt, 1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (bt, 128), 1)
    # before[j, i] = 1 where lane j comes before lane i
    before = (jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0) <
              jax.lax.broadcasted_iota(jnp.int32, (128, 128), 1)
              ).astype(jnp.float32)

    def up(x, s):                                          # to higher lanes
        return pltpu.roll(x, s % 128, 1)

    def bit(x, b):
        return ((x >> b) & 1) == 1

    idx_ref[...] = jnp.zeros_like(idx_ref)

    def chunk(i, c, count):
        sc = s_ref[0, i, :, c * 128:(c + 1) * 128]        # [bt, 128]
        key = order_key(sc)
        pos = i * keys + c * 128 + lane
        sel = (sc > NEG_INF) & ((key > thr) | ((key == thr) & (pos <= last)))
        gap = jnp.dot(jnp.where(sel, 0.0, 1.0), before,
                      preferred_element_type=jnp.float32).astype(jnp.int32)
        for b in range(7):
            go = sel & bit(gap, b)
            come = up(go.astype(jnp.int32), -(1 << b)) == 1
            pos = jnp.where(come, up(pos, -(1 << b)), pos)
            gap = jnp.where(come, up(gap, -(1 << b)), gap)
            sel = come | (sel & ~go)
        n = jnp.sum(sel.astype(jnp.int32), axis=1, keepdims=True)
        m, t = count & 127, count >> 7
        for b in range(7):
            pos = jnp.where(bit(m, b), up(pos, 1 << b), pos)
        tile = jnp.where((lane >= m) & (lane < m + n), t,
                         jnp.where(lane < m + n - 128, t + 1, -1))
        for j in range(tiles):
            idx_ref[j] = jnp.where(tile == j, pos, idx_ref[j])
        return count + n

    def block(i, count):
        for c in range(keys // 128):
            count = chunk(i, c, count)
        return count

    n_ref[...] = jax.lax.fori_loop(0, nlive_ref[0], block,
                                   jnp.zeros((bt, 1), jnp.int32))


def topk_rows_pallas(scores, k: int, q_offset, valid, interpret=False):
    """`topk_rows` of decode scores [B, nb, 1, keys] by counting: the B rows
    laid out as the rows of the threshold kernel's tiles ([1, nb, B', keys],
    B' a multiple of 8, the walk bounded by the largest live position), its
    (thr, last), then `_compact_kernel`.  The same sets as `topk_rows`, in
    ascending position order."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, nb, T, keys = scores.shape
    assert T == 1, "the decode form takes one query token a slot"
    rows = -(-B // 8) * 8
    bt = _threshold_rows(rows)
    s = jnp.pad(scores.reshape(B, nb, keys).transpose(1, 0, 2),
                ((0, 0), (0, rows - B), (0, 0)),
                constant_values=NEG_INF)[None]             # [1, nb, rows, keys]
    valid = jnp.asarray(valid, jnp.int32)
    end = jnp.max(jnp.where(valid > 0,
                            jnp.asarray(q_offset, jnp.int32) + valid, 0))
    # the threshold kernel's tile ends at q_offset + bt - 1: the last position
    thr, last = topk_threshold_pallas(s, k, (end - bt)[None], interpret)
    K = min(k, nb * keys)
    tiles = -(-K // 128)
    n_live = jnp.minimum(-(-end // keys), nb).astype(jnp.int32)[None]
    row = pl.BlockSpec((1, bt, 1), lambda t, nl: (0, t, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,          # live blocks
        grid=(rows // bt,),
        in_specs=[pl.BlockSpec((1, nb, bt, keys),
                               lambda t, nl: (0, 0, t, 0)), row, row],
        out_specs=[pl.BlockSpec((tiles, bt, 128), lambda t, nl: (0, t, 0)),
                   pl.BlockSpec((bt, 1), lambda t, nl: (t, 0))],
    )
    idx, n = pl.pallas_call(
        functools.partial(_compact_kernel, keys=keys),
        name="topk_compact",
        grid_spec=grid_spec,
        out_shape=[_out_struct((tiles, rows, 128), jnp.int32, scores),
                   _out_struct((rows, 1), jnp.int32, scores)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(n_live, s, thr, last)
    idx = idx.transpose(1, 0, 2).reshape(rows, tiles * 128)[:B, :K]
    return idx.reshape(B, 1, K), n[:B]


def topk_rows_decode(scores, k: int, q_offset, valid):
    """The decode form's top-k (T = 1, `models.hybrid.dsa_attention`):
    `topk_rows_pallas` on a TPU where the layout suits it, the sort
    (`topk_rows`) otherwise."""
    if _on_tpu() and scores.shape[-1] % 128 == 0:
        return topk_rows_pallas(scores, k, q_offset, valid)
    return topk_rows(scores, k)


# ---------------------------------------------------------------------------
# absorbed attention over chosen rows
# ---------------------------------------------------------------------------

def sparse_latent_attention_xla(q, rows, n_sel, latent: int, scale: float):
    """q [N, H, W] per head [q_lat | q_rope | 0..]; rows [N, K, W] the
    latent rows chosen for each query token, the first n_sel [N] of them
    real.  Returns [N, H, latent]."""
    K = rows.shape[1]
    s = jnp.einsum("nhw,nkw->nhk", q, rows,
                   preferred_element_type=jnp.float32) * scale
    ok = jnp.arange(K)[None, None, :] < n_sel[:, None, None]
    p = jax.nn.softmax(jnp.where(ok, s, NEG_INF), axis=-1)
    return jnp.einsum("nhk,nkc->nhc", p.astype(rows.dtype),
                      rows[..., :latent])


def _sparse_kernel(n_ref, q_ref, r_ref, o_ref, *, latent: int, scale: float):
    """One query token's chosen rows [K, W] as a block.  XLA's gather writes
    them where it keeps the kernel's operands, in VMEM for most layers at the
    cell's size (`S(1)` in the compiled program), so
    `benchmarks/work/dsa.sparse_latent` counts the kernel's operations
    alone."""
    from jax.experimental import pallas as pl
    n = pl.program_id(0)
    K = r_ref.shape[1]
    ok = jax.lax.broadcasted_iota(jnp.int32, (K, 1), 0) < n_ref[n]
    k = jnp.where(ok, r_ref[0], jnp.zeros_like(r_ref[0]))       # [K, W]
    s = jnp.dot(q_ref[0], k.T, preferred_element_type=jnp.float32) * scale
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < n_ref[n], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    o = jnp.dot(p.astype(k.dtype), k[:, :latent],
                preferred_element_type=jnp.float32)
    o_ref[0] = (o / l).astype(o_ref.dtype)


def sparse_latent_attention_pallas(q, rows, n_sel, latent: int, scale: float,
                                   interpret=False):
    """One grid step a query token: its chosen rows in VMEM whole, one
    softmax over them.  (A form that copied the rows from HBM itself made
    XLA move them out of VMEM first: the decode step took 22.8 ms against
    13.2, my chip runs, PR 39.)"""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, H, W = q.shape
    K = rows.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,          # n_sel
        grid=(N,),
        in_specs=[pl.BlockSpec((1, H, W), lambda n, ns: (n, 0, 0)),
                  pl.BlockSpec((1, K, W), lambda n, ns: (n, 0, 0))],
        out_specs=pl.BlockSpec((1, H, latent), lambda n, ns: (n, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_sparse_kernel, latent=latent, scale=scale),
        name="sparse_latent",
        grid_spec=grid_spec,
        out_shape=_out_struct((N, H, latent), q.dtype, q, rows),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(jnp.asarray(n_sel, jnp.int32), q, rows)


def sparse_latent_attention(q, rows, n_sel, latent: int, scale: float):
    """Absorbed attention over the chosen rows only (`models.hybrid`'s
    decode form of a DSA layer): the kernel on a TPU where the layout suits
    it, the oracle otherwise."""
    if _on_tpu() and latent % 128 == 0 and q.shape[-1] % 128 == 0 \
            and q.shape[1] % 8 == 0 and rows.shape[1] % 128 == 0:
        return sparse_latent_attention_pallas(q, rows, n_sel, latent, scale)
    return sparse_latent_attention_xla(q, rows, n_sel, latent, scale)


# ---------------------------------------------------------------------------
# the masked form expanded: per-head keys and values, a long pass
# ---------------------------------------------------------------------------
# A query block of a long pass weighs its chosen keys in the EXPANDED form,
# per head k_h = [W^K_h c ; k_rope] and v_h = W^V_h c, so a pair and head
# costs 2 (nope + rope) + 2 v_head_dim operations (1,024 at GLM-5's
# 256 | 256) where the absorbed walk spends 2 lane + 2 latent (2,304).  The
# keys are built once a layer by the caller (`models.hybrid._expanded_keys`);
# the values are formed a key block at a time from the latent rows inside
# the kernel (2 latent v_head_dim operations a key, head and query tile:
# 1/8 of the tile's attention at 2,048 queries), because at a 32k prompt
# the values of every head would hold another 1.07 GB beside the keys'.
# The selection reaches the kernel as one keep byte a (token, key), read
# once a head: the float32 scores a head would read are four.

# query rows a grid step holds; the keys of a block of its walk are the
# scores' own blocks, so that key block j is block j of the keep mask
_EXPANDED_Q_ROWS = 2048


def masked_expanded_attention_xla(q, k, c, wv, q_offset, select,
                                  scale: float):
    """Attention of T query tokens a slot over their chosen keys, expanded
    (fallback + oracle).  q [B, T, H, D]; k [B, H, D, S]: per head the keys
    of the slot's positions 0 .. S - 1, the position minor (as their product
    writes them); c [B, S, >= latent] the same positions' latent rows and
    wv [H, latent, Dv] W^V, whose product is each head's values; `select` as
    `paged_latent_attention`'s (a row weighs exactly the keys `mask_from`
    keeps, never one past its own position).  Returns [B, T, H, Dv]."""
    B, T, H, _ = q.shape
    S = k.shape[-1]
    v = jnp.einsum("bsc,hcv->bhsv", c[:, :S, :wv.shape[1]], wv)
    logits = jnp.einsum("bthd,bhds->bhts", q, k,
                        preferred_element_type=jnp.float32) * scale
    qpos = q_offset[:, None] + jnp.arange(T)
    mask = unblocked(mask_from(*select))[:, :, :S] & \
        (jnp.arange(S)[None, None] <= qpos[:, :, None])
    p = jax.nn.softmax(jnp.where(mask[:, None], logits, NEG_INF), axis=-1)
    return jnp.einsum("bhts,bhsv->bthv", p.astype(v.dtype), v)


def _expanded_kernel(qoff_ref, q_ref, wv_ref, k_hbm, c_hbm, keep_hbm, o_ref,
                     kbuf, cbuf, mbuf, sem, acc_ref, m_ref, l_ref, *, bq: int,
                     bk: int, n_k: int, scale: float):
    """Grid (B, T/bq, H): a slot, a tile of bq query tokens and a head a
    step.  The walk runs over the key blocks up to the tile's last position
    only (causal), block j + 1's copies in flight while block j is weighed:
    the head's keys k [B, H, D, S] (the block's columns), the block's latent
    rows (their first `latent` columns; times the head's W^V, its values)
    and the tile's rows of block j of the keep mask.  A row with no kept key
    in a block passes it on the finite NEG_INF; the next kept key's
    correction erases what that added (every real row keeps its own
    position)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, i, h = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    last = qoff_ref[b] + i * bq + bq - 1
    n_live = jnp.minimum(last // bk + 1, n_k)
    latent = cbuf.shape[-1]

    def copies(j, slot):
        keys = pl.ds(j * bk, bk)
        return (pltpu.make_async_copy(k_hbm.at[b, h, :, keys],
                                      kbuf.at[slot], sem.at[0, slot]),
                pltpu.make_async_copy(c_hbm.at[b, keys, pl.ds(0, latent)],
                                      cbuf.at[slot], sem.at[1, slot]),
                pltpu.make_async_copy(keep_hbm.at[b, j, pl.ds(i * bq, bq)],
                                      mbuf.at[slot], sem.at[2, slot]))

    for cp in copies(0, 0):
        cp.start()
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    q = q_ref[0]                                        # [bq, D]
    wv = wv_ref[0]                                      # [latent, Dv]

    def block(j, carry):
        slot = j % 2

        @pl.when(j + 1 < n_live)
        def _next():
            for cp in copies(j + 1, 1 - slot):
                cp.start()

        for cp in copies(j, slot):
            cp.wait()
        v = jnp.dot(cbuf[slot], wv, preferred_element_type=jnp.float32
                    ).astype(wv.dtype)                  # [bk, Dv]
        s = jnp.dot(q, kbuf[slot], preferred_element_type=jnp.float32) \
            * scale                                     # [bq, bk]
        s = jnp.where(mbuf[slot].astype(jnp.int32) != 0, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_live, block, 0)
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                ).astype(o_ref.dtype)


def _expanded_rows(T: int) -> int:
    return next((b for b in (_EXPANDED_Q_ROWS, 1024, 512, 256, 128, 64, 32)
                 if T % b == 0), 0)


def masked_expanded_attention_pallas(q, k, c, wv, q_offset, select,
                                     scale: float, interpret=False):
    """The kernel behind `masked_expanded_attention` (same contract as the
    oracle; T a multiple of 32).  q and the output lie [B, T, H * width]
    (the layouts the query's product writes and W^O reads), a head's query
    tile a block of whole 128-lane columns."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, D = q.shape
    latent, Dv = wv.shape[1:]
    keep = mask_from(*select).astype(jnp.int8)         # [B, nb, T, keys]
    nb, keys = keep.shape[1], keep.shape[-1]
    if k.shape[-1] < nb * keys:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, nb * keys - k.shape[-1])))
    if c.shape[1] < nb * keys:
        c = jnp.pad(c, ((0, 0), (0, nb * keys - c.shape[1]), (0, 0)))
    bq = _expanded_rows(T)
    tile = lambda b, i, h, qo: (b, i, h)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,          # q_offset
        grid=(B, T // bq, H),
        in_specs=[pl.BlockSpec((1, bq, D), tile),
                  pl.BlockSpec((1, latent, Dv),
                               lambda b, i, h, qo: (h, 0, 0)),
                  pl.BlockSpec(memory_space=pltpu.HBM),
                  pl.BlockSpec(memory_space=pltpu.HBM),
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec((1, bq, Dv), tile),
        scratch_shapes=[pltpu.VMEM((2, D, keys), k.dtype),
                        pltpu.VMEM((2, keys, latent), c.dtype),
                        pltpu.VMEM((2, bq, keys), jnp.int8),
                        pltpu.SemaphoreType.DMA((3, 2)),
                        pltpu.VMEM((bq, Dv), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_expanded_kernel, bq=bq, bk=keys, n_k=nb,
                          scale=scale),
        name="masked_expanded",
        grid_spec=grid_spec,
        out_shape=_out_struct((B, T, H * Dv), q.dtype, q, k, c),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=96 << 20),
        interpret=interpret,
    )(jnp.asarray(q_offset, jnp.int32), q.reshape(B, T, H * D), wv, k, c,
      keep)
    return out.reshape(B, T, H, Dv)


def masked_expanded_attention(q, k, c, wv, q_offset, select, scale: float):
    """A long DSA pass's attention over each token's chosen keys in the
    expanded form (`models.hybrid.dsa_attention`): the kernel on a TPU where
    the layout suits it (whole 128-lane heads and latents, whole query
    tiles), the oracle otherwise."""
    if _on_tpu() and q.shape[-1] % 128 == 0 and wv.shape[-1] % 128 == 0 \
            and wv.shape[1] % 128 == 0 and _expanded_rows(q.shape[1]):
        return masked_expanded_attention_pallas(q, k, c, wv, q_offset, select,
                                                scale)
    return masked_expanded_attention_xla(q, k, c, wv, q_offset, select, scale)
