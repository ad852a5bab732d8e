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
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import _on_tpu

# rows per tile; the row count is padded to a multiple of it
ROW_TILE = 128


def _tiling(k: int, n: int):
    """(rows, contraction, columns) per tile: the whole contraction in one
    tile where its [k, tn] weight block (double-buffered) stays well inside
    the 16 MiB of scoped VMEM, so a group's weights are fetched in a few
    large blocks and not in hundreds of 128 x 128 ones."""
    tk = k if k <= 4096 else 2048
    tn = 512 if n > 512 else n
    return ROW_TILE, tk, tn


def grouped_matmul(lhs, rhs, group_sizes, first: int = 0,
                   transpose_rhs: bool = False):
    """lhs [M, K] sorted by group; rhs [E_here, K, N] ([E_here, N, K] with
    `transpose_rhs`); group_sizes [E_total] int32; `first` the (static)
    global id of rhs[0].  Returns [M, N] in lhs's dtype."""
    M, K = lhs.shape
    E = rhs.shape[0]
    N = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    if _on_tpu():
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        pad = -M % ROW_TILE
        if pad:
            lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
        out = gmm(lhs, rhs, group_sizes.astype(jnp.int32),
                  preferred_element_type=lhs.dtype, tiling=_tiling(K, N),
                  group_offset=jnp.asarray(first, jnp.int32),
                  transpose_rhs=transpose_rhs)
        return out[:M]
    # ragged_dot counts rows from 0 for its first group: bring the first held
    # group's rows to the top, multiply, and put them back
    if transpose_rhs:
        rhs = jnp.swapaxes(rhs, 1, 2)
    start = jnp.sum(group_sizes[:first])
    out = jax.lax.ragged_dot(jnp.roll(lhs, -start, axis=0), rhs,
                             group_sizes[first:first + E].astype(jnp.int32))
    return jnp.roll(out, start, axis=0)
