"""The expert layer's grouped products in a TRAINING step, per call: `pairs`
rows (token-expert pairs that fell on held experts) against the [K, N]
matrices of the `experts` held, every one of which gets rows.

`gmm` is the rows' product, forward (rows [pairs, K] -> [pairs, N]) and in
the backward pass for the rows' gradient (the same product against the
transposed matrices: the same operations and bytes with K and N swapped);
`tgmm` is the matrices' gradient, one [K, N] product per expert over its
rows.  At 1,024 rows an expert and [2048, 768]: 3.2 G operations (16.4 us at
the chip's peak) against 3.1 MB of matrix and 5.8 MB of rows (10.9 us at its
bandwidth): compute-bound, the bytes two thirds of the least time."""
from __future__ import annotations


def gmm(pairs, experts, K, N, itemsize=2):
    return {"flops": 2 * pairs * K * N,
            "bytes": (experts * K * N + pairs * (K + N)) * itemsize}


def tgmm(pairs, experts, K, N, itemsize=2):
    """Reads both row matrices, writes the experts' [K, N] gradients."""
    return {"flops": 2 * pairs * K * N,
            "bytes": (pairs * (K + N) + experts * K * N) * itemsize}
