"""The paged attention kernel of the serving step: what one call has to do for
`live_tokens` cached tokens in all (summed over the slots of the batch) and
`queries` query rows: read each live token's key and value once, and two
matmuls against them."""
from __future__ import annotations


def serve_attention(live_tokens, queries, H, KVH, hd, itemsize=2):
    kv_bytes = 2 * live_tokens * KVH * hd * itemsize
    q_bytes = 2 * queries * H * hd * itemsize          # q in, out back
    # every query row of a slot attends to that slot's live tokens; with one
    # row per slot (decode) the pairs are `live_tokens` per query head
    return {"flops": 2 * 2 * live_tokens * H * hd,
            "bytes": kv_bytes + q_bytes}
