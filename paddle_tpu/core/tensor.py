"""The eager Tensor and the op-dispatch layer.

Reference parity: the public `paddle::Tensor` handle (`paddle/phi/api/include/tensor.h:82`)
plus `AutogradMeta` (`paddle/fluid/eager/autograd_meta.h:61`) and the generated
`*_ad_func` dispatch (`eager/auto_code_generator/generator/eager_gen.py:214`) that wraps
every phi API with GradNode creation.

TPU-native design: `Tensor` wraps a `jnp.ndarray` (device buffer managed by XLA — the
reference's allocator/DeviceContext layers collapse into the XLA runtime).  `apply()` is
the single dispatch point every op goes through: it decides whether to record a GradNode
(capturing the pullback via `jax.vjp`) and wraps outputs.  AMP autocast and the NaN/Inf
checker hook in here, mirroring the AMP_LOGIC / nan_inf_utils stages of the generated
ad_func.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import autograd as _ag
from . import dtype as _dt
from . import flags as _flags
from .place import CPUPlace, Place, TPUPlace, _get_expected_place


def _to_data(x, dtype=None):
    """Anything -> jnp array."""
    if isinstance(x, Tensor):
        return x._data
    if isinstance(x, (jnp.ndarray, jax.Array)):
        return x
    return jnp.asarray(x, dtype=_dt.to_np(dtype) if dtype is not None else None)


class Tensor:
    """Eager tensor: a jnp device array + autograd metadata."""

    # keep Tensor light: one data slot + autograd meta (AutogradMeta parity)
    # hot fields get slots; __dict__ stays for cold metadata (dist axes, marks)
    __slots__ = ("_data", "stop_gradient", "grad", "_grad_node", "_out_index",
                 "persistable", "name", "_backward_hooks", "trainable",
                 "is_distributed", "_optimize_attrs", "_retain_grad", "__weakref__",
                 "__dict__")

    _name_counter = 0

    def __init__(self, data=None, dtype=None, place=None, stop_gradient=True, name=None):
        if data is None:
            data = jnp.zeros((), _dt.to_np(dtype or _dt._default_dtype))
        d = _to_data(data, dtype)
        if dtype is not None and d.dtype != _dt.to_np(dtype):
            d = d.astype(_dt.to_np(dtype))
        if isinstance(place, CPUPlace):
            d = jax.device_put(d, place.jax_device())
        self._data = d
        self.stop_gradient = bool(stop_gradient)
        self.grad = None
        self._grad_node = None
        self._out_index = 0
        self.persistable = False
        self.trainable = True
        self.is_distributed = False
        self._optimize_attrs = {}
        self._backward_hooks = []
        self._version = 0  # inplace version counter (ref inplace_version)
        if name is None:
            Tensor._name_counter += 1
            name = f"generated_tensor_{Tensor._name_counter}"
        self.name = name

    # ---- structural properties ----
    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def ndim(self):
        return self._data.ndim

    dim = ndim

    @property
    def dtype(self):
        return _dt.convert_dtype(self._data.dtype)

    @property
    def size(self):
        return int(self._data.size)

    def numel(self):
        return int(self._data.size)

    @property
    def place(self):
        try:
            dev = list(self._data.devices())[0]
        except Exception:
            return CPUPlace()
        if dev.platform == "tpu":
            return TPUPlace(dev.id)
        return CPUPlace()

    @property
    def is_leaf(self):
        return self._grad_node is None

    @property
    def T(self):
        # paddle semantics: reverse ALL dimensions (fluid/dygraph/math_op_patch.py:174)
        return apply("t", lambda x: jnp.transpose(x), self)

    @property
    def mT(self):
        return apply("mT", lambda x: jnp.swapaxes(x, -2, -1) if x.ndim >= 2 else x, self)

    # ---- conversion ----
    def numpy(self):
        return np.asarray(self._data)

    def __array__(self, dtype=None):
        a = np.asarray(self._data)
        return a.astype(dtype) if dtype is not None else a

    def item(self, *args):
        if args:
            return np.asarray(self._data).item(*args)
        return np.asarray(self._data).item()

    def tolist(self):
        return np.asarray(self._data).tolist()

    def astype(self, dtype):
        npd = _dt.to_np(dtype)
        return apply("cast", lambda x: x.astype(npd), self)

    cast = astype

    def clone(self):
        return apply("clone", lambda x: x + jnp.zeros((), x.dtype) if jnp.issubdtype(x.dtype, jnp.inexact) else jnp.array(x), self)

    def detach(self):
        t = Tensor.__new__(Tensor)
        t._data = self._data
        t.stop_gradient = True
        t.grad = None
        t._grad_node = None
        t._out_index = 0
        t.persistable = False
        t.trainable = True
        t.is_distributed = False
        t._optimize_attrs = {}
        t._backward_hooks = []
        t.name = self.name + ".detach"
        return t

    def cpu(self):
        return Tensor(jax.device_put(self._data, jax.devices("cpu")[0]),
                      stop_gradient=self.stop_gradient)

    def tpu(self):
        return Tensor(jax.device_put(self._data, _get_expected_place().jax_device()),
                      stop_gradient=self.stop_gradient)

    cuda = tpu  # compat: accelerator move

    def pin_memory(self):
        return self.cpu()

    def to(self, *args, **kwargs):
        t = self
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, (str,)) and a in ("cpu",):
                t = t.cpu()
            elif isinstance(a, str) and a.split(":")[0] in ("tpu", "gpu", "cuda", "xpu"):
                t = t.tpu()
            elif isinstance(a, Place):
                t = t.cpu() if isinstance(a, CPUPlace) else t.tpu()
            else:
                try:
                    t = t.astype(a)
                except Exception:
                    pass
        return t

    # ---- autograd surface ----
    def backward(self, grad_tensor=None, retain_graph=False):
        _ag.run_backward([self], [grad_tensor], retain_graph)

    def register_hook(self, hook):
        self._backward_hooks.append(hook)
        if self._grad_node is not None:
            # non-leaf: the engine consults hooks via the producing node's out_refs
            self._grad_node.register_output_ref(self)

        class _Handle:
            def remove(h_self):
                try:
                    self._backward_hooks.remove(hook)
                except ValueError:
                    pass

        return _Handle()

    def clear_grad(self, set_to_zero=False):
        if set_to_zero and self.grad is not None:
            self.grad._data = jnp.zeros_like(self.grad._data)
        else:
            self.grad = None

    clear_gradient = clear_grad

    def retain_grads(self):
        """Retain .grad on a non-leaf tensor (reference Tensor.retain_grads)."""
        if self._grad_node is None:
            return  # leaf: engine writes .grad anyway
        self._retain_grad = True
        self._grad_node.register_output_ref(self)

    @property
    def gradient(self):
        return None if self.grad is None else self.grad.numpy()

    def _grad_ivar(self):
        return self.grad

    # ---- python protocol ----
    def __len__(self):
        if self._data.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._data.shape[0]

    def __repr__(self):
        prefix = "Tensor(shape={}, dtype={}, place={}, stop_gradient={},\n       ".format(
            self.shape, self.dtype.name, self.place, self.stop_gradient)
        body = np.array2string(np.asarray(self._data), prefix=" " * 7)
        return prefix + body + ")"

    def __bool__(self):
        if self._data.size != 1:
            raise ValueError("The truth value of a multi-element Tensor is ambiguous")
        return bool(np.asarray(self._data))

    def __int__(self):
        return int(np.asarray(self._data))

    def __float__(self):
        return float(np.asarray(self._data))

    def __index__(self):
        return int(np.asarray(self._data))

    def __format__(self, spec):
        if self._data.size == 1:
            return format(self.item(), spec)
        return object.__format__(self, spec)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __hash__(self):
        return id(self)

    def __dlpack__(self, stream=None):
        return self._data.__dlpack__()

    def __dlpack_device__(self):
        return self._data.__dlpack_device__()

    # ---- indexing ----
    def _norm_index(self, idx):
        def conv(i):
            if isinstance(i, Tensor):
                return i._data
            if isinstance(i, (list, np.ndarray)):
                return jnp.asarray(i)
            return i
        if isinstance(idx, tuple):
            return tuple(conv(i) for i in idx)
        return conv(idx)

    def __getitem__(self, idx):
        nidx = self._norm_index(idx)
        return apply("slice", lambda x: x[nidx], self)

    def __setitem__(self, idx, value):
        nidx = self._norm_index(idx)
        vt = value if isinstance(value, Tensor) else Tensor(_to_data(value), stop_gradient=True)
        # In-place scatter: self becomes the output of a set_value node whose inputs are
        # a shadow of the old self and the value (reference: set_value op + inplace
        # version bump; prior readers of self in the live tape are not version-checked).
        prev = self.detach()
        prev.stop_gradient = self.stop_gradient
        prev._grad_node = self._grad_node
        prev._out_index = self._out_index
        vdata = vt._data
        if vdata.dtype != self._data.dtype and vdata.dtype.kind == self._data.dtype.kind:
            vt = vt.astype(self._data.dtype)
        def _setfn(x, v):
            tgt_shape = x[nidx].shape
            v = v.astype(x.dtype)
            if v.shape != tgt_shape:
                if v.size == int(np.prod(tgt_shape)):
                    v = v.reshape(tgt_shape)
                else:
                    v = jnp.broadcast_to(v, tgt_shape)
            return x.at[nidx].set(v)
        out = apply("set_value", _setfn, prev, vt)
        self._data = out._data
        self._grad_node = out._grad_node
        self._out_index = out._out_index
        self.stop_gradient = out.stop_gradient
        self._version += 1  # prior tape readers of self now error in backward

    # ---- arithmetic dunders (full set; implementations are jnp lambdas) ----
    def __add__(self, o):
        return apply("add", jnp.add, self, o)

    def __radd__(self, o):
        return apply("add", jnp.add, o, self)

    def __sub__(self, o):
        return apply("subtract", jnp.subtract, self, o)

    def __rsub__(self, o):
        return apply("subtract", jnp.subtract, o, self)

    def __mul__(self, o):
        return apply("multiply", jnp.multiply, self, o)

    def __rmul__(self, o):
        return apply("multiply", jnp.multiply, o, self)

    def __truediv__(self, o):
        return apply("divide", jnp.true_divide, self, o)

    def __rtruediv__(self, o):
        return apply("divide", jnp.true_divide, o, self)

    def __floordiv__(self, o):
        return apply("floor_divide", jnp.floor_divide, self, o)

    def __rfloordiv__(self, o):
        return apply("floor_divide", jnp.floor_divide, o, self)

    def __mod__(self, o):
        return apply("remainder", jnp.remainder, self, o)

    def __rmod__(self, o):
        return apply("remainder", jnp.remainder, o, self)

    def __pow__(self, o):
        return apply("pow", jnp.power, self, o)

    def __rpow__(self, o):
        return apply("pow", jnp.power, o, self)

    def __matmul__(self, o):
        return apply("matmul", jnp.matmul, self, o)

    def __rmatmul__(self, o):
        return apply("matmul", jnp.matmul, o, self)

    def __neg__(self):
        return apply("neg", jnp.negative, self)

    def __abs__(self):
        return apply("abs", jnp.abs, self)

    def __invert__(self):
        return apply("invert", jnp.invert, self)

    # comparison (stop_gradient outputs)
    def __eq__(self, o):
        return apply("equal", jnp.equal, self, o)

    def __ne__(self, o):
        return apply("not_equal", jnp.not_equal, self, o)

    def __lt__(self, o):
        return apply("less_than", jnp.less, self, o)

    def __le__(self, o):
        return apply("less_equal", jnp.less_equal, self, o)

    def __gt__(self, o):
        return apply("greater_than", jnp.greater, self, o)

    def __ge__(self, o):
        return apply("greater_equal", jnp.greater_equal, self, o)

    def __and__(self, o):
        return apply("bitwise_and", jnp.bitwise_and, self, o)

    def __or__(self, o):
        return apply("bitwise_or", jnp.bitwise_or, self, o)

    def __xor__(self, o):
        return apply("bitwise_xor", jnp.bitwise_xor, self, o)

    # in-place variants (trailing-underscore, paddle style): rebind data
    def _inplace_from(self, out: "Tensor"):
        node = out._grad_node
        if node is not None:
            # the producing node recorded *this object* as its input; after the
            # rebind that would be a self-loop in the tape (and a stale read).
            # Swap in a snapshot carrying the pre-op state (reference: eager
            # inplace version snapshot in TensorWrapper).
            snap = None
            for i, inp in enumerate(node.inputs):
                if inp is self:
                    if snap is None:
                        snap = Tensor(self._data, stop_gradient=self.stop_gradient)
                        snap._grad_node = self._grad_node
                        snap._out_index = self._out_index
                        snap._version = self._version
                    node.inputs[i] = snap
        self._data = out._data
        self._grad_node = out._grad_node
        self._out_index = out._out_index
        self._version += 1
        return self

    def add_(self, o):
        return self._inplace_from(self.__add__(o))

    def subtract_(self, o):
        return self._inplace_from(self.__sub__(o))

    def multiply_(self, o):
        return self._inplace_from(self.__mul__(o))

    def divide_(self, o):
        return self._inplace_from(self.__truediv__(o))

    def scale_(self, scale=1.0, bias=0.0):
        return self._inplace_from(apply("scale", lambda x: x * scale + bias, self))

    def zero_(self):
        self._data = jnp.zeros_like(self._data)
        self._version += 1
        return self

    def fill_(self, value):
        self._data = jnp.full_like(self._data, value)
        self._version += 1
        return self

    def copy_(self, other, blocking=True):
        self._data = _to_data(other).astype(self._data.dtype)
        self._version += 1
        return self

    def set_value(self, value):
        self._data = _to_data(value).astype(self._data.dtype)
        self._version += 1  # stale tape readers must error, same as copy_
        return self

    # value state used by optimizers/Layer
    def _is_initialized(self):
        return True


class Parameter(Tensor):
    """Trainable tensor (paddle.framework.Parameter parity): stop_gradient=False."""

    def __init__(self, data=None, dtype=None, name=None, trainable=True):
        super().__init__(data, dtype=dtype, stop_gradient=not trainable, name=name)
        self.persistable = True
        self.trainable = trainable


EagerParamBase = Parameter  # reference alias


# ---------------------------------------------------------------------------
# op dispatch
# ---------------------------------------------------------------------------

_amp_state = None  # set by paddle_tpu.amp to an active autocast state or None


def _set_amp_state(state):
    global _amp_state
    _amp_state = state


# static-graph recorder slot: when paddle.enable_static() is on, every apply()
# also appends (name, jfn, inputs, outputs) to the current static Program so
# Executor.run can re-execute the graph with feed substitution (the TPU-native
# ProgramDesc: the recorded eager tape IS the program)
_static_recorder = [None]


def apply(name: str, jfn: Callable, *inputs, n_outputs: Optional[int] = None,
          _data_override: Optional[Sequence] = None) -> Any:
    """Single dispatch point for every eager op.

    Mirrors the generated ad_func pipeline (`eager_gen.py:214`): AMP cast -> forward ->
    optional NaN check -> GradNode capture via jax.vjp when any input requires grad.
    `jfn` consumes/produces jnp arrays; attrs are closed over by the caller.
    `_data_override`: per-slot replacement arrays (None = use the input's data) —
    used by the create_graph replay to linearize at the forward-time primals while
    keeping the original tensor objects as graph edges.
    """
    if _amp_state is not None and _amp_state.enabled:
        inputs = _amp_state.cast_inputs(name, inputs)

    datas = [_to_data(x) for x in inputs]
    if _data_override is not None:
        datas = [d if ov is None else ov
                 for d, ov in zip(datas, _data_override)]

    need_grad = _ag.is_grad_enabled() and any(
        isinstance(x, Tensor) and not x.stop_gradient
        and jnp.issubdtype(x._data.dtype, jnp.inexact)
        for x in inputs)

    if not need_grad:
        out = jfn(*datas)
        res = _wrap_outputs(name, out, node=None)
        if _static_recorder[0] is not None:
            _static_recorder[0]._record(name, jfn, inputs, res)
        return res

    outs, vjp_fn = jax.vjp(jfn, *datas)
    tensor_inputs = [x if isinstance(x, Tensor) else None for x in inputs]
    multi = isinstance(outs, (tuple, list))
    out_list = list(outs) if multi else [outs]
    specs = [(o.shape, o.dtype) for o in out_list]
    node = _ag.GradNode(name, vjp_fn, tensor_inputs, len(out_list), specs,
                        jfn=jfn, in_datas=datas, out_tuple=multi)
    res = _wrap_outputs(name, outs, node=node)
    if _static_recorder[0] is not None:
        _static_recorder[0]._record(name, jfn, inputs, res)
    return res


def _wrap_outputs(name, out, node):
    if _flags.flag("check_nan_inf"):
        _check_numerics(name, out)
    if isinstance(out, (tuple, list)):
        res = []
        for i, o in enumerate(out):
            t = Tensor(o)
            if node is not None and jnp.issubdtype(o.dtype, jnp.inexact):
                t.stop_gradient = False
                t._grad_node = node
                t._out_index = i
            res.append(t)
        return tuple(res)
    t = Tensor(out)
    if node is not None and jnp.issubdtype(out.dtype, jnp.inexact):
        t.stop_gradient = False
        t._grad_node = node
        t._out_index = 0
    return t


def _check_numerics(name, out):
    """FLAGS_check_nan_inf parity (`fluid/eager/nan_inf_utils.h:38`)."""
    outs = out if isinstance(out, (tuple, list)) else [out]
    for o in outs:
        if jnp.issubdtype(jnp.asarray(o).dtype, jnp.inexact):
            bad = bool(jnp.any(~jnp.isfinite(o)))
            if bad:
                msg = f"Operator {name} output contains NaN/Inf"
                if _flags.flag("check_nan_inf_level") == 0:
                    raise FloatingPointError(msg)
                print("WARNING:", msg)


def to_tensor(data, dtype=None, place=None, stop_gradient=True):
    """paddle.to_tensor."""
    if isinstance(data, Tensor):
        t = data.astype(dtype) if dtype is not None else Tensor(data._data)
        t.stop_gradient = stop_gradient
        return t
    if dtype is None and isinstance(data, (float,)):
        dtype = _dt._default_dtype
    if dtype is None and isinstance(data, (list, tuple)):
        flat = np.asarray(data)
        if flat.dtype == np.float64:
            dtype = _dt._default_dtype
    if dtype is None and isinstance(data, np.ndarray) and data.dtype == np.float64:
        dtype = _dt.float64  # paddle keeps fp64 numpy as fp64
    return Tensor(data, dtype=dtype, place=place, stop_gradient=stop_gradient)
