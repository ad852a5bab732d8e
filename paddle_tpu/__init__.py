"""paddle_tpu — a TPU-native deep learning framework with PaddlePaddle's capability
surface, built on JAX/XLA/Pallas.

Public API mirrors `python/paddle/__init__.py` of the reference; implementations are
idiomatic TPU (XLA kernels, GSPMD parallelism, jaxpr program capture) rather than ports.
"""
from __future__ import annotations

import os as _os

import jax as _jax

# Sharding-invariant RNG: the same seed must draw the same params on every
# mesh, or the dp/mp-vs-single-device parity the parallel trainers advertise
# breaks.  Process-global; an explicit JAX_THREEFRY_PARTITIONABLE env
# setting wins (see README).
if "JAX_THREEFRY_PARTITIONABLE" not in _os.environ:
    _jax.config.update("jax_threefry_partitionable", True)

# ---- core ----
from .core import dtype as _dtype_mod
from .core.dtype import (bool_ as bool, uint8, int8, int16, int32, int64, float16,  # noqa
                         bfloat16, float32, float64, complex64, complex128,
                         set_default_dtype, get_default_dtype)
from .core.place import (CPUPlace, CUDAPinnedPlace, CUDAPlace, CustomPlace, Place,  # noqa
                         TPUPlace, XPUPlace, set_device, get_device, device_count,
                         is_compiled_with_cuda, is_compiled_with_rocm,
                         is_compiled_with_tpu, is_compiled_with_xpu)
from .core.tensor import Tensor, to_tensor  # noqa
from .core.autograd import no_grad, enable_grad, set_grad_enabled, is_grad_enabled  # noqa
from .core.generator import seed, get_rng_state_tracker  # noqa
from .core.flags import get_flags, set_flags  # noqa
from .core import generator as _generator

# ---- ops: flatten the functional namespace like paddle.* ----
from .ops.creation import (arange, assign, clone, complex, create_parameter, diag,  # noqa
                           diag_embed, diagflat, empty, empty_like, eye, full,
                           full_like, linspace, logspace, meshgrid, ones, ones_like,
                           polar, tril, tril_indices, triu, triu_indices, zeros,
                           zeros_like)
from .ops.math import (abs, acos, acosh, accuracy, add, addmm, all, amax, amin,  # noqa
                       angle, any, asin, asinh, atan, atan2, atanh, bmm,
                       broadcast_shape, ceil, clip, conj, copysign, cos, cosh,
                       count_nonzero, cross, cumprod, cummax, cummin, cumsum,
                       deg2rad, diagonal, diff, digamma, divide, dot,
                       erf, erfinv, exp, expm1, floor, floor_divide, floor_mod, fmax,
                       fmin, frac, gcd, heaviside, hypot, i0, i0e, i1, i1e, imag,
                       increment, inner, isfinite, isinf, isnan, isneginf, isposinf,
                       isreal, kron, lcm, ldexp, lerp, lgamma, log, log10, log1p,
                       log2, logaddexp, logcumsumexp, logsumexp, matmul, max, maximum,
                       mean, min, minimum, mm, mod, multiplex, multiply, mv, nan_to_num,
                       nanmean, nansum, neg, nextafter, outer, polygamma, pow, prod,
                       rad2deg, real, reciprocal, remainder, round, rsqrt, scale, sgn,
                       sign, sin, sinh, sqrt, square, stanh, subtract, sum, t, take,
                       tan, tanh, trace, trunc)
from .ops.manipulation import (as_complex, as_real, as_strided, atleast_1d,  # noqa
                               atleast_2d, atleast_3d, broadcast_tensors, broadcast_to,
                               cast, chunk, concat, crop, expand, expand_as, flatten,
                               flip, gather, gather_nd, index_add, index_put,
                               index_sample, index_select, is_complex, is_empty,
                               is_floating_point, is_integer, is_tensor, masked_fill,
                               masked_fill_, masked_select, moveaxis, nonzero, numel,
                               pad, put_along_axis, rank, repeat_interleave, reshape,
                               reshape_, roll, rot90, scatter, scatter_, scatter_nd,
                               scatter_nd_add, shape, shard_index, slice, split,
                               squeeze, squeeze_, stack, strided_slice, swapaxes,
                               take_along_axis, tensor_split, tile, transpose, unbind,
                               unique, unique_consecutive, unsqueeze, unsqueeze_,
                               unstack, view, view_as, where, where_)
from .ops.logic import (allclose, bitwise_and, bitwise_not, bitwise_or, bitwise_xor,  # noqa
                        equal, equal_all, greater_equal, greater_than, isclose,
                        less_equal, less_than, logical_and, logical_not, logical_or,
                        logical_xor, not_equal)
from .ops.random import (bernoulli, bernoulli_, binomial, cauchy_, exponential_,  # noqa
                         gaussian, geometric_, get_cuda_rng_state, get_rng_state,
                         log_normal_, multinomial, normal, normal_, poisson, rand,
                         rand_like, randint, randint_like, randn, randn_like, randperm,
                         set_cuda_rng_state, set_rng_state, standard_normal, uniform,
                         uniform_)
from .ops.search import (argmax, argmin, argsort, bucketize, kthvalue, mode,  # noqa
                         searchsorted, sort, topk)
from .ops.stat import median, nanmedian, nanquantile, quantile, std, var  # noqa
from .ops.linalg import (bincount, cdist, cholesky, cholesky_solve, cond, corrcoef,  # noqa
                         cov, det, dist, eig, eigh, eigvals, eigvalsh, histogram,
                         histogramdd, householder_product, inverse, lstsq, lu,
                         matrix_power, matrix_rank, multi_dot, norm, pdist, pinv, qr,
                         slogdet, solve, svd, triangular_solve)
from .ops.einsum import einsum  # noqa
from .ops.math import (add_n, cumulative_trapezoid, frexp, logit, renorm,  # noqa
                       sigmoid, trapezoid)
from .ops.manipulation import reverse, unflatten, unfold, vsplit  # noqa
from .ops.linalg import lu_unpack, pca_lowrank, tensordot  # noqa
from .ops.creation import create_tensor, vander  # noqa
from .ops.inplace import *  # noqa  (trailing-underscore in-place variants)

from .param_attr import ParamAttr  # noqa
from .framework.io import save, load  # noqa
from .autograd import grad, backward  # noqa
from .utils.dlpack import to_dlpack, from_dlpack  # noqa

# ---- subpackages (paddle.nn style access) ----
from . import amp  # noqa
from . import audio  # noqa
from . import autograd  # noqa
from . import distributed  # noqa
from . import distribution  # noqa
from . import fft  # noqa
from . import geometric  # noqa
from . import signal  # noqa
from . import text  # noqa
from . import framework  # noqa
from . import incubate  # noqa
from . import io  # noqa
from . import jit  # noqa
from . import linalg  # noqa
from . import metric  # noqa
from . import nn  # noqa
from . import optimizer  # noqa
from . import inference  # noqa
from . import onnx  # noqa
from . import profiler  # noqa
from . import quantization  # noqa
from . import sparse  # noqa
from . import static  # noqa
from . import utils  # noqa
from . import vision  # noqa

from .jit import to_static  # noqa
from .distributed import DataParallel  # noqa
from .hapi.model import Model  # noqa

# dygraph flag compat: we are always in dygraph (eager) mode unless static capture
_in_dynamic = True


def in_dynamic_mode():
    return _in_dynamic


def disable_static():
    global _in_dynamic
    _in_dynamic = True
    static._disable_static_recording()


def enable_static():
    global _in_dynamic
    _in_dynamic = False
    static._enable_static_recording()


def disable_signal_handler():
    pass


def device(dev):  # paddle.device module shim is in utils; keep callable
    return set_device(dev)


class finfo:
    """ref paddle.finfo: floating-point type limits."""

    def __init__(self, dtype):
        import jax.numpy as _jnp
        from .core.dtype import to_np as _to_np
        fi = _jnp.finfo(_to_np(dtype))
        self.min = float(fi.min)
        self.max = float(fi.max)
        self.eps = float(fi.eps)
        self.tiny = float(fi.tiny)
        self.smallest_normal = float(fi.tiny)
        self.resolution = float(fi.resolution)
        self.bits = int(fi.bits)
        self.dtype = str(fi.dtype)


class iinfo:
    """ref paddle.iinfo: integer type limits."""

    def __init__(self, dtype):
        import jax.numpy as _jnp
        from .core.dtype import to_np as _to_np
        ii = _jnp.iinfo(_to_np(dtype))
        self.min = int(ii.min)
        self.max = int(ii.max)
        self.bits = int(ii.bits)
        self.dtype = str(ii.dtype)


dtype = _dtype_mod.DType  # paddle.dtype type object (ref VarType alias)


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """ref paddle.set_printoptions — forwards to numpy's print options."""
    import numpy as _np
    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    _np.set_printoptions(**kw)


def tolist(x):
    """ref paddle.tolist: nested python list of tensor values."""
    import numpy as _np
    return _np.asarray(x.numpy() if hasattr(x, "numpy") else x).tolist()


class LazyGuard:
    """ref paddle.LazyGuard: delayed parameter init context.  Eager jax init is
    cheap, so this is a transparent shim (params materialize immediately)."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def batch(reader, batch_size, drop_last=False):
    """ref paddle.batch (legacy reader decorator)."""
    def batched():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf
    return batched


def check_shape(x):
    """ref static nn.check_shape helper (shape sanity assert shim)."""
    return x


def summary(net, input_size=None, dtypes=None, input=None):
    from .hapi.summary import summary as _s
    return _s(net, input_size, dtypes, input)


def flops(net, input_size, custom_ops=None, print_detail=False):
    from .hapi.flops import flops as _f
    return _f(net, input_size, custom_ops, print_detail)


def _patch_tensor_methods():
    """Attach the functional namespace as Tensor methods, like the reference's
    monkey-patch in `python/paddle/fluid/dygraph/tensor_patch_methods.py`."""
    import sys
    mod = sys.modules[__name__]
    from .ops import (creation, inplace, linalg, logic, manipulation, math,
                      random, search, stat)
    from .ops.einsum import einsum as _einsum  # noqa

    method_sources = [math, manipulation, logic, search, stat, linalg, creation,
                      random, inplace]
    skip = {"broadcast_shape", "create_parameter", "meshgrid", "is_tensor",
            "get_rng_state", "set_rng_state", "get_cuda_rng_state", "set_cuda_rng_state"}
    for src in method_sources:
        for name in dir(src):
            if name.startswith("_") or name in skip:
                continue
            fn = getattr(src, name)
            if not callable(fn):
                continue
            if not hasattr(Tensor, name):
                setattr(Tensor, name, fn)
    # explicit overrides where method semantics differ slightly
    Tensor.norm = linalg.norm
    Tensor.matmul = math.matmul
    Tensor.reshape = manipulation.reshape
    Tensor.cast = manipulation.cast

    # sparse conversions (ref Tensor.to_sparse_coo / to_sparse_csr / to_dense)
    def _to_sparse_coo(self, sparse_dim=None):
        from .sparse import _dense_to_coo
        return _dense_to_coo(self, sparse_dim)

    def _to_sparse_csr(self):
        from .sparse import _dense_to_coo
        return _dense_to_coo(self).to_sparse_csr()

    Tensor.to_sparse_coo = _to_sparse_coo
    Tensor.to_sparse_csr = _to_sparse_csr
    Tensor.to_dense = lambda self: self
    Tensor.is_sparse = lambda self: False
    Tensor.is_sparse_coo = lambda self: False
    Tensor.is_sparse_csr = lambda self: False


_patch_tensor_methods()

__version__ = "0.1.0"
version = type("version", (), {"full_version": __version__,
                               "commit": "tpu-native",
                               "cuda": staticmethod(lambda: None),
                               "show": staticmethod(lambda: print(__version__))})
