"""The flash attention kernels at a score width `D` (q and k) that differs
from the value width `Dv` (v, out, dO): operations and bytes per call, causal,
from the call's shape [B, S, H, .] — latent attention expanded, 192 against
128.  Whatever the kernel stores or pads, these count D and Dv."""
from __future__ import annotations


def _pairs(B, S, H):
    # (query, key) pairs under the causal mask, diagonal included
    return B * H * S * (S + 1) / 2


def fwd(B, S, H, D, Dv, itemsize=2):
    """q k^T (D wide) and p v (Dv wide); reads q k v, writes out."""
    return {"flops": 2 * _pairs(B, S, H) * (D + Dv),
            "bytes": 2 * B * S * H * (D + Dv) * itemsize}


def bwd_dkv(B, S, H, D, Dv, itemsize=2):
    """Recomputes s = q k^T (D), then dv = p^T do (Dv), dp = do v^T (Dv),
    dk = ds^T q (D); reads q k v do, writes dk dv."""
    return {"flops": 2 * _pairs(B, S, H) * (2 * D + 2 * Dv),
            "bytes": 3 * B * S * H * (D + Dv) * itemsize}


def bwd_dq(B, S, H, D, Dv, itemsize=2):
    """Recomputes s (D), then dp = do v^T (Dv), dq = ds k (D); reads q k v
    do, writes dq."""
    return {"flops": 2 * _pairs(B, S, H) * (2 * D + Dv),
            "bytes": B * S * H * (3 * D + 2 * Dv) * itemsize}
