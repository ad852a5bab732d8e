"""The flash attention kernels' operations and bytes per call, causal, from
the call's shape [B, S, H, hd] (q, k and v alike after the GQA repeat)."""
from __future__ import annotations


def _pairs(B, S, H):
    # (query, key) pairs under the causal mask, diagonal included
    return B * H * S * (S + 1) / 2


def fwd(B, S, H, hd, itemsize=2):
    """q k^T and p v: two matmuls."""
    return {"flops": 2 * 2 * _pairs(B, S, H) * hd,
            "bytes": 4 * B * S * H * hd * itemsize}


def bwd_dkv(B, S, H, hd, itemsize=2):
    """Recomputes s = q k^T, then dv = p^T do, dp = do v^T, dk = ds^T q:
    four matmuls; reads q k v do, writes dk dv."""
    return {"flops": 4 * 2 * _pairs(B, S, H) * hd,
            "bytes": 6 * B * S * H * hd * itemsize}


def bwd_dq(B, S, H, hd, itemsize=2):
    """Recomputes s, then dp = do v^T, dq = ds k: three matmuls; reads
    q k v do, writes dq."""
    return {"flops": 3 * 2 * _pairs(B, S, H) * hd,
            "bytes": 5 * B * S * H * hd * itemsize}
