"""The expert layer's grouped matrix product: one call multiplies `pairs`
rows (token-expert pairs that fell on held experts), each by its expert's
[K, N] matrix, and has to read the matrices of the `experts_touched` distinct
experts that got a row, once each.  In decode a few rows meet many experts:
bandwidth-bound."""
from __future__ import annotations


def grouped_matmul(pairs, experts_touched, K, N, itemsize=2):
    return {"flops": 2 * pairs * K * N,
            "bytes": experts_touched * K * N * itemsize +
            pairs * (K + N) * itemsize}


def expert_flops_per_pair(D, F) -> int:
    """Up- and down-projection of one token through one (ungated) expert."""
    return 2 * 2 * D * F
