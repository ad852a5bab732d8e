"""The Mamba-2 state update of one decode step: what it has to do for
`slots` live slots in one layer.  Per state element [H, P, N]: decay it, add
the new outer product, and dot it with C (six operations), and read and write
it once in float32.  Bandwidth-bound."""
from __future__ import annotations


def update(slots, H, P, N, state_itemsize=4):
    elems = slots * H * P * N
    return {"flops": 6 * elems, "bytes": 2 * elems * state_itemsize}


def scan_flops_per_token(H, P, N) -> int:
    """The recurrence's operations for one position of one layer, however it
    is computed (position by position or in chunks)."""
    return 6 * H * P * N
