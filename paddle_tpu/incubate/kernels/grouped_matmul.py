"""Grouped matrix product for a dropless expert layer: rows sorted by group,
each group multiplied by its own weight matrix.

    out[r] = lhs[r] @ rhs[g - first]   for the rows r of group g,
                                       first <= g < first + rhs.shape[0]

(`transpose_rhs`: rhs holds each matrix as [N, K] and the product is with its
transpose.  An expert's up-projection is kept that way: a [K, N] matrix whose
N is no multiple of 128 gets a transposed layout on the chip, and the kernel,
which wants rows of N, would be handed a copy of all the experts each call.)

`group_sizes` counts the rows of EVERY group (all of the router's experts);
`rhs` holds only the groups [first, first + E_here) this chip has.  Rows of
groups outside that range, and rows past sum(group_sizes), come back as
whatever the kernel left there: the caller masks them (it knows which
token-expert pairs fell on absent experts).

On a TPU this is the megablox kernel that ships with JAX
(`jax.experimental.pallas.ops.tpu.megablox.gmm`): it visits only the
(group, row-tile) pairs that hold rows, so a decode step streams each touched
expert's weights once and an untouched expert's not at all.  Elsewhere it is
`jax.lax.ragged_dot` over the held groups.

The product is differentiable in `lhs` and `rhs`.  On a TPU the backward is
megablox's own pair — `gmm` against the transposed matrices for the rows'
gradient, `tgmm` (one [K, N] product per group over that group's rows) for
the matrices' — with one repair: the rows' gradient is ZERO outside the held
groups' rows, where the bare kernel would leave whatever memory held (and a
caller's gather would add that to real tokens).  Elsewhere `ragged_dot`
brings its own rule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import _on_tpu

# rows per tile; the row count is padded to a multiple of it.  A serving step
# has a few rows an expert; a training step has hundreds, and at 128 rows a
# tile each [K, tn] weight block would be fetched again for every 128 rows:
# 128 operations a byte, under the chip's ridge.
ROW_TILE = 128
TRAIN_ROW_TILE = 512


def _tiling(k: int, n: int, tm: int = ROW_TILE):
    """(rows, contraction, columns) per tile: the whole contraction in one
    tile where its [k, tn] weight block (double-buffered) stays well inside
    the 16 MiB of scoped VMEM, so a group's weights are fetched in a few
    large blocks and not in hundreds of 128 x 128 ones."""
    tk = k if k <= 4096 else 2048
    tn = 512 if n > 512 else n
    return tm, tk, tn


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _gmm_tpu(lhs, rhs, group_sizes, first: int, transpose_rhs: bool,
             tm: int = ROW_TILE, interpret: bool = False):
    """lhs [M, K] with M a multiple of `tm`, through megablox."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    K = lhs.shape[1]
    N = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
               tiling=_tiling(K, N, tm),
               group_offset=jnp.asarray(first, jnp.int32),
               transpose_rhs=transpose_rhs, interpret=interpret)


def _gmm_tpu_fwd(lhs, rhs, group_sizes, first, transpose_rhs, tm, interpret):
    return _gmm_tpu(lhs, rhs, group_sizes, first, transpose_rhs, tm,
                    interpret), (lhs, rhs, group_sizes)


def _gmm_tpu_bwd(first, transpose_rhs, tm, interpret, res, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm
    lhs, rhs, group_sizes = res
    M, K = lhs.shape
    E = rhs.shape[0]
    N = g.shape[1]
    offset = jnp.asarray(first, jnp.int32)
    with jax.named_scope("gmm_bwd_rows"):
        d_lhs = gmm(g, rhs, group_sizes, preferred_element_type=lhs.dtype,
                    tiling=_tiling(N, K, tm), group_offset=offset,
                    transpose_rhs=not transpose_rhs, interpret=interpret)
        ends = jnp.cumsum(group_sizes)
        start = ends[first - 1] if first else 0
        r = jnp.arange(M)[:, None]
        d_lhs = jnp.where((r >= start) & (r < ends[first + E - 1]), d_lhs, 0)
    with jax.named_scope("gmm_bwd_weights"):
        # [K, M] x [M, N] per group -> [E, K, N]
        d_rhs = tgmm(lhs.swapaxes(0, 1), g, group_sizes,
                     preferred_element_type=rhs.dtype,
                     tiling=_tiling(K, N, tm),
                     group_offset=offset, num_actual_groups=E,
                     interpret=interpret)
        if transpose_rhs:
            d_rhs = d_rhs.swapaxes(1, 2)
    return d_lhs, d_rhs, None


_gmm_tpu.defvjp(_gmm_tpu_fwd, _gmm_tpu_bwd)


def grouped_matmul(lhs, rhs, group_sizes, first: int = 0,
                   transpose_rhs: bool = False, row_tile: int = ROW_TILE):
    """lhs [M, K] sorted by group; rhs [E_here, K, N] ([E_here, N, K] with
    `transpose_rhs`); group_sizes [E_total] int32; `first` the (static)
    global id of rhs[0]; `row_tile` the kernel's rows per tile.  Returns
    [M, N] in lhs's dtype."""
    M, K = lhs.shape
    E = rhs.shape[0]
    N = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    if _on_tpu():
        pad = -M % row_tile
        if pad:
            lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
        out = _gmm_tpu(lhs, rhs, group_sizes.astype(jnp.int32), first,
                       transpose_rhs, row_tile)
        return out[:M]
    # ragged_dot counts rows from 0 for its first group: bring the first held
    # group's rows to the top, multiply, and put them back
    if transpose_rhs:
        rhs = jnp.swapaxes(rhs, 1, 2)
    start = jnp.sum(group_sizes[:first])
    out = jax.lax.ragged_dot(jnp.roll(lhs, -start, axis=0), rhs,
                             group_sizes[first:first + E].astype(jnp.int32))
    return jnp.roll(out, start, axis=0)
