"""Fused RMSNorm Pallas kernel (reference: `phi/kernels/fusion/gpu/
fused_rms_norm_kernel`).

Row-tiled: each program normalizes a [block_rows, D] tile in VMEM — one HBM read, one
write.  Backward is the standard analytic pullback, expressed in jnp (XLA fuses it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import _on_tpu, _out_struct


def _rms_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[:] = (x * jax.lax.rsqrt(var + eps)).astype(o_ref.dtype) * w_ref[:]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_pallas(x2d, w, eps):
    return _rms_fwd_impl(x2d, w, eps)


def _rms_fwd_impl(x2d, w, eps):
    from jax.experimental import pallas as pl

    N, D = x2d.shape
    block = 256
    while N % block != 0:
        block //= 2
    block = max(block, 1)
    return pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        name="rms_norm",
        grid=(N // block,),
        in_specs=[pl.BlockSpec((block, D), lambda i: (i, 0)),
                  pl.BlockSpec((D,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block, D), lambda i: (i, 0)),
        out_shape=_out_struct((N, D), x2d.dtype, x2d, w),
    )(x2d, w)


def _rms_ref(x, w, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def _rms_fwd(x2d, w, eps):
    return _rms_fwd_impl(x2d, w, eps), (x2d, w)


def _rms_bwd(eps, res, g):
    x2d, w = res
    _, vjp = jax.vjp(lambda x_, w_: _rms_ref(x_, w_, eps), x2d, w)
    return vjp(g)


_rms_pallas.defvjp(_rms_fwd, _rms_bwd)


def rms_norm_fused(x, w, eps=1e-6):
    """x: [..., D]; w: [D]."""
    D = x.shape[-1]
    if _on_tpu() and D % 128 == 0 and x.size // D >= 8:
        x2d = x.reshape(-1, D)
        out = _rms_pallas(x2d, w, eps)
        return out.reshape(x.shape)
    return _rms_ref(x, w, eps)
