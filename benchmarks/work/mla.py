"""The latent (MLA) paged attention kernel in its absorbed form: what one
call has to do for `live_tokens` cached rows in all (summed over the slots of
the batch) and `rows` query tokens of `H` heads.  Each live row is [latent |
rope] numbers and is read once; every query head scores it over all latent +
rope columns and weighs its latent columns as values.  The work is the
mathematics': the zero columns the lane is padded with (to whole 128-lane
tiles) are neither counted as bytes nor as operations, so a kernel that
spends time on them reads a lower share."""
from __future__ import annotations


def absorbed_attention(live_tokens, rows, H, latent, rope, itemsize=2):
    """`live_tokens`: rows that lay written behind the call's active slots
    (engine counter `latent_tokens_written`, not reserved pages); with one
    query token a slot (decode) the (query head, key) pairs are live_tokens x
    H."""
    cache_bytes = live_tokens * (latent + rope) * itemsize
    q_bytes = rows * H * ((latent + rope) + latent) * itemsize   # q in, o out
    return {"flops": 2 * live_tokens * H * ((latent + rope) + latent),
            "bytes": cache_bytes + q_bytes}
