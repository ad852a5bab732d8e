"""Mamba-2 state-space kernels (Dao & Gu 2024, "Transformers are SSMs"): the
chunked scan a prompt runs through and the one-token state update of decode.

Per head h (group g = h // (H // G)), with a_t = dt_t * A_h <= 0:

    S_t = exp(a_t) * S_{t-1} + dt_t * x_t (outer) B_{g,t}        S: [P, N]
    y_t = S_t C_{g,t}                                  (the D x_t skip is the caller's)

A position with dt_t = 0 leaves the state as it was and adds nothing to it:
that is how callers keep bucket padding and inactive slots out of the state.

`ssm_chunk_scan` computes the recurrence in chunks of `chunk` positions (the
SSD form): inside a chunk the outputs are one masked [Q, Q] product, between
chunks the state is carried by a `lax.scan`; `ssm_update` is the recurrence
itself for one position.  Both keep the state in float32.  They are plain
`jax.numpy`: XLA fuses the update into one pass over the state, and the scan's
products are MXU work.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ssm_update(x, dt, A, Bm, Cm, state):
    """One position.  x [B, H, P]; dt [B, H] float32 (softplus already
    applied, 0 where the slot must not move); A [H] (negative); Bm, Cm
    [B, G, N]; state [B, H, P, N] float32.  Returns (y [B, H, P] float32,
    new state)."""
    B, H, P = x.shape
    G = Bm.shape[1]
    R = H // G
    f32 = jnp.float32
    decay = jnp.exp(dt * A.astype(f32))                          # [B, H]
    xdt = x.astype(f32) * dt[..., None]                          # [B, H, P]
    Bh = jnp.repeat(Bm.astype(f32), R, axis=1)                   # [B, H, N]
    Ch = jnp.repeat(Cm.astype(f32), R, axis=1)
    state = state * decay[..., None, None] + \
        xdt[..., None] * Bh[:, :, None, :]
    y = jnp.sum(state * Ch[:, :, None, :], axis=-1)              # [B, H, P]
    return y, state


def ssm_chunk_scan(x, dt, A, Bm, Cm, state, chunk: int = 128):
    """T positions from `state`.  x [B, T, H, P]; dt [B, T, H] float32; A [H];
    Bm, Cm [B, T, G, N]; state [B, H, P, N] float32.  Returns (y [B, T, H, P]
    float32, state after position T-1).  T need not be a multiple of `chunk`:
    the tail is padded with dt = 0 positions, which do not move the state."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    R = H // G
    f32 = jnp.float32
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                                 (a.ndim - 2)) for a in (x, dt, Bm, Cm))
    nc = (T + pad) // Q
    a = (dt * A.astype(f32)).reshape(B, nc, Q, G, R)
    cum = jnp.cumsum(a, axis=2)                                  # <= 0
    xdt = (x.astype(f32) * dt[..., None]).reshape(B, nc, Q, G, R, P)
    Bc = Bm.astype(f32).reshape(B, nc, Q, G, N)
    Cc = Cm.astype(f32).reshape(B, nc, Q, G, N)
    # inside a chunk: y_q += sum_{s<=q} exp(cum_q - cum_s) (C_q . B_s) dt_s x_s
    cb = jnp.einsum("bcqgn,bcsgn->bcgqs", Cc, Bc,
                    preferred_element_type=f32)
    cq = jnp.moveaxis(cum, 2, -1)                                # [B,nc,G,R,Q]
    seg = cq[..., :, None] - cq[..., None, :]                    # [.., q, s]
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    m = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0) * \
        cb[:, :, :, None]
    y = jnp.einsum("bcgrqs,bcsgrp->bcqgrp", m, xdt,
                   preferred_element_type=f32)
    # what each chunk adds to the state by its end, and how far it decays it
    to_end = jnp.exp(cum[:, :, -1:] - cum)                       # [B,nc,Q,G,R]
    ds = jnp.einsum("bcsgn,bcsgrp->bcgrpn", Bc, xdt * to_end[..., None],
                    preferred_element_type=f32)
    chunk_decay = jnp.exp(cum[:, :, -1])                         # [B,nc,G,R]

    def carry(s, inp):
        dec, add = inp
        return s * dec[..., None, None] + add, s                 # emits S_{c-1}

    s0 = state.reshape(B, G, R, P, N)
    s_last, s_prev = jax.lax.scan(
        carry, s0, (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(ds, 1, 0)))
    s_prev = jnp.moveaxis(s_prev, 0, 1)                          # [B,nc,G,R,P,N]
    # across chunks: y_q += exp(cum_q) C_q . S_{c-1}
    y = y + jnp.einsum("bcqgn,bcgrpn->bcqgrp", Cc, s_prev,
                       preferred_element_type=f32) * \
        jnp.exp(cum)[..., None]
    y = y.reshape(B, nc * Q, H, P)[:, :T]
    return y, s_last.reshape(B, H, P, N)
