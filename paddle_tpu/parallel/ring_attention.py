"""Ring attention — context parallelism for long sequences.

Beyond-reference capability (SURVEY §7.10): the reference's long-context story
is flash-attn + Megatron SP + recompute; ring/blockwise attention (Liu et al.
2023) is the idiomatic TPU mechanism: shard the SEQUENCE over a `cp` mesh axis,
keep q local, and rotate k/v shards around the ring with `ppermute` while
accumulating blockwise-softmax partial results — attention memory per chip
drops from O(S^2) to O((S/cp)^2) and the k/v transfer overlaps with compute on
ICI.

Design: the chunk loop is a `lax.scan` whose carry holds the circulating k/v
chunk and the online-softmax state (o, m, l).  `jax.grad` differentiates
through the scan and transposes each `ppermute` into the reverse-ring permute,
yielding the standard ring-attention backward (dk/dv circulate backwards)
without a hand-written schedule.  Each chunk's blockwise compute is
`jax.checkpoint`ed so backward memory stays at one chunk of logits.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def ring_attention_local(q, k, v, axis_name: str, cp: int, causal: bool = True,
                         scale=None):
    """Runs INSIDE a manual region over `axis_name` (cp ranks).

    q, k, v: [B, S_local, H, D] — this rank's sequence shard (global sequence
    order follows rank order).  Returns [B, S_local, H, D].
    """
    B, Sl, H, D = q.shape
    s = scale if scale is not None else 1.0 / math.sqrt(D)
    r = jax.lax.axis_index(axis_name)
    qpos = r * Sl + jnp.arange(Sl)

    qt = jnp.transpose(q, (0, 2, 1, 3))                 # [B, H, Sl, D]

    def blockwise(qt_, kc, vc, o, m, l, kpos):
        """One k/v chunk folded into the online-softmax state."""
        sblk = jnp.einsum("bhqd,bkhd->bhqk", qt_, kc,
                          preferred_element_type=jnp.float32) * s
        if causal:
            mask = qpos[:, None] >= kpos[None, :]
            sblk = jnp.where(mask[None, None], sblk, NEG_INF)
        m_cur = jnp.max(sblk, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(sblk - m_new[..., None])
        if causal:
            # fully-masked rows: exp(NEG-NEG)=1 must not leak mass
            p = jnp.where(mask[None, None], p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        return o_new, m_new, l_new

    blockwise = jax.checkpoint(blockwise)

    def step(carry, t):
        kc, vc, o, m, l = carry
        src = (r - t) % cp                              # chunk's origin rank
        kpos = src * Sl + jnp.arange(Sl)
        o, m, l = blockwise(qt, kc, vc, o, m, l, kpos)
        # rotate the k/v chunk one step around the ring
        perm = [(i, (i + 1) % cp) for i in range(cp)]
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return (kc, vc, o, m, l), None

    # the scan carry must vary over the same manual axes as q
    vma = tuple(jax.typeof(q).vma) or (axis_name,)
    o0 = jax.lax.pcast(jnp.zeros((B, H, Sl, D), jnp.float32), vma,
                       to="varying")
    m0 = jax.lax.pcast(jnp.full((B, H, Sl), NEG_INF, jnp.float32), vma,
                       to="varying")
    l0 = jax.lax.pcast(jnp.zeros((B, H, Sl), jnp.float32), vma, to="varying")

    (kf, vf, o, m, l), _ = jax.lax.scan(step, (k, v, o0, m0, l0),
                                        jnp.arange(cp))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.transpose(out.astype(q.dtype), (0, 2, 1, 3))
    # named so remat_policy_save_attention saves the ring output: block replay
    # under cfg.remat must not re-run the cp-step scan + ppermutes
    from jax.ad_checkpoint import checkpoint_name
    return checkpoint_name(out, "flash_out")


def ring_attention(q, k, v, mesh, axis_name: str = "cp", causal: bool = True,
                   scale=None):
    """GSPMD entry: q, k, v [B, S, H, D] with S sharded over `axis_name`."""
    cp = mesh.shape[axis_name]
    fn = functools.partial(ring_attention_local, axis_name=axis_name, cp=cp,
                           causal=causal, scale=scale)
    spec = P(None, axis_name, None, None)
    return jax.shard_map(lambda a, b, c: fn(a, b, c), mesh=mesh,
                         axis_names={axis_name},
                         in_specs=(spec, spec, spec),
                         out_specs=spec)(q, k, v)
