"""NDArray: the imperative n-dimensional array over ``jax.Array``.

TPU-native re-design of the reference's NDArray
(`include/mxnet/ndarray.h`, `src/ndarray/ndarray.cc`; Python surface
`python/mxnet/ndarray/ndarray.py` — file-level citations, SURVEY.md caveat).

Where the reference pairs each NDArray with an engine variable and pushes
every op into a threaded dependency engine (SURVEY.md §1 invariant), here the
async contract is inherited from XLA: ``jax.Array`` dispatch is asynchronous,
``asnumpy()`` is the sync point (the reference's ``WaitToRead``), and
ordering/races are owned by the compiler+runtime rather than a scheduler.
The dependency engine is therefore *absent by design* (SURVEY.md §7.3).
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as _np

from ..base import MXNetError
from ..context import Context, current_context

__all__ = ["NDArray", "_wrap", "_as_jax"]

_DTYPE_ALIASES = {
    "float32": jnp.float32, "float64": jnp.float64, "float16": jnp.float16,
    "bfloat16": jnp.bfloat16, "uint8": jnp.uint8, "int8": jnp.int8,
    "int32": jnp.int32, "int64": jnp.int64, "bool": jnp.bool_,
    "uint32": jnp.uint32, "uint64": jnp.uint64, "int16": jnp.int16,
}


def _to_jnp_dtype(dtype):
    if dtype is None:
        return None
    if isinstance(dtype, str):
        if dtype not in _DTYPE_ALIASES:
            raise MXNetError(f"unknown dtype {dtype!r}")
        return _DTYPE_ALIASES[dtype]
    return dtype


def _as_jax(x, dtype=None):
    """Convert array-like/NDArray/scalar to a jax array."""
    if isinstance(x, NDArray):
        arr = x._data
    elif isinstance(x, jax.Array):
        arr = x
    else:
        arr = jnp.asarray(x, dtype=_to_jnp_dtype(dtype) or (
            jnp.float32 if isinstance(x, (list, tuple, float)) or (
                isinstance(x, _np.ndarray) and x.dtype == _np.float64) else None))
    if dtype is not None:
        arr = arr.astype(_to_jnp_dtype(dtype))
    return arr


def _wrap(data) -> "NDArray":
    return NDArray(data)


class NDArray:
    """An n-dimensional, device-resident, asynchronously-evaluated array.

    Construct via factory functions (``mx.nd.array``, ``mx.nd.zeros`` …);
    the constructor takes a raw ``jax.Array``.
    """

    __slots__ = ("_data", "_ag_node", "_ag_idx", "_ag_grad", "_ag_grad_req",
                 "_fresh", "_ov_member", "__weakref__")

    def __init__(self, data):
        if isinstance(data, NDArray):
            data = data._data
        self._data = data
        self._ag_node = None
        self._ag_idx = 0
        self._ag_grad = None
        self._ag_grad_req = "write"

    # ------------------------------------------------------------------ #
    # basic properties
    # ------------------------------------------------------------------ #
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def size(self) -> int:
        return int(self._data.size)

    @property
    def ndim(self) -> int:
        return self._data.ndim

    @property
    def context(self) -> Context:
        try:
            dev = list(self._data.devices())[0]
        except Exception:
            return current_context()
        if dev.platform == "cpu":
            # In CPU-only processes the host devices double as virtual
            # accelerators (see context.py); report tpu ctx there so
            # device-placement code behaves uniformly.
            if all(d.platform == "cpu" for d in jax.devices()):
                return Context("tpu", dev.id)
            return Context("cpu", dev.id)
        return Context("tpu", dev.id)

    ctx = context

    @property
    def grad(self) -> Optional["NDArray"]:
        return self._ag_grad

    @property
    def stype(self) -> str:
        return "default"

    # ------------------------------------------------------------------ #
    # sync / host transfer
    # ------------------------------------------------------------------ #
    def asnumpy(self) -> _np.ndarray:
        """Copy to host (the sync point — reference ``WaitToRead`` +
        ``MXNDArraySyncCopyToCPU``)."""
        return _np.asarray(jax.device_get(self._data))

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(()).item()

    def item(self):
        return self.asscalar()

    def wait_to_read(self):
        """Block until the value is computed (reference ``WaitToRead``)."""
        jax.block_until_ready(self._data)
        return self

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __bool__(self):
        if self.size == 0:
            return False
        if self.size == 1:
            return bool(self.asscalar())
        raise MXNetError("ambiguous truth value of multi-element NDArray")

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of 0-d NDArray")
        return self.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __repr__(self):
        return f"\n{self.asnumpy()!r}\n<NDArray {('x'.join(map(str, self.shape)) or 'scalar')} @{self.context}>"

    # ------------------------------------------------------------------ #
    # autograd
    # ------------------------------------------------------------------ #
    def attach_grad(self, grad_req: str = "write", stype=None):
        """Allocate a gradient buffer; marks this array as a differentiation
        variable (detaches any recorded history, matching the reference)."""
        self._ag_node = None
        self._ag_grad = NDArray(jnp.zeros(self.shape, self.dtype))
        self._ag_grad_req = grad_req

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        from .. import autograd
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    def detach(self) -> "NDArray":
        return NDArray(self._data)

    # ------------------------------------------------------------------ #
    # placement / conversion
    # ------------------------------------------------------------------ #
    def as_in_context(self, context: Context) -> "NDArray":
        if not isinstance(context, Context):
            raise MXNetError("as_in_context expects a Context")
        return NDArray(jax.device_put(self._data, context.jax_device))

    as_in_ctx = as_in_context

    def copyto(self, other: Union[Context, "NDArray"]) -> "NDArray":
        if isinstance(other, Context):
            return self.as_in_context(other)
        other._data = jax.device_put(self._data.astype(other.dtype),
                                     list(other._data.devices())[0])
        return other

    def copy(self) -> "NDArray":
        return NDArray(jnp.copy(self._data))

    def astype(self, dtype, copy=True) -> "NDArray":
        d = _to_jnp_dtype(dtype)
        if not copy and self.dtype == d:
            return self
        from .. import autograd as _ag
        if _ag.is_recording():
            # route through the registered Cast op so the dtype change
            # lands on the tape — a bare jnp astype severs gradient
            # flow through every mixed-precision forward
            return self._op("cast", dtype=d)
        return NDArray(self._data.astype(d))

    def tostype(self, stype: str) -> "NDArray":
        if stype == "default":
            return self
        from . import sparse as _sp
        return _sp.cast_storage(self, stype)

    # ------------------------------------------------------------------ #
    # indexing
    # ------------------------------------------------------------------ #
    @staticmethod
    def _index_leaf(k):
        """Array indexers: float dtypes are POSITIONS and cast to int32
        (the reference's take-convention for ndarray indices); boolean
        masks are rejected with a pointer at nd.boolean_mask (their
        data-dependent output shape cannot trace under jit)."""
        if isinstance(k, NDArray):
            k = k._data
        if hasattr(k, "dtype") and hasattr(k, "ndim"):
            if k.dtype == jnp.bool_:
                raise MXNetError(
                    "boolean-mask indexing has a data-dependent shape; "
                    "use nd.boolean_mask(data, mask) (or nd.where) "
                    "instead")
            if jnp.issubdtype(k.dtype, jnp.floating):
                k = k.astype(jnp.int32)
        return k

    def _index(self, key):
        if isinstance(key, tuple):
            return tuple(self._index_leaf(k) for k in key)
        return self._index_leaf(key)

    def __getitem__(self, key):
        from .register import invoke_by_name
        return invoke_by_name("_slice_index", self, index=self._index(key))

    def __setitem__(self, key, value):
        val = _as_jax(value, dtype=self.dtype) if not isinstance(value, NDArray) \
            else value._data.astype(self.dtype)
        if isinstance(key, slice) and key == slice(None):
            self._data = jnp.broadcast_to(val, self.shape)
        else:
            self._data = self._data.at[self._index(key)].set(val)

    # ------------------------------------------------------------------ #
    # arithmetic — delegates into the op registry so autograd records it
    # ------------------------------------------------------------------ #
    def _binop(self, name, other, reverse=False):
        from .register import invoke_by_name
        if not isinstance(other, NDArray):
            other = NDArray(_as_jax(other, dtype=None).astype(self.dtype)
                            if _np.isscalar(other) or isinstance(other, (int, float))
                            else _as_jax(other))
        a, b = (other, self) if reverse else (self, other)
        return invoke_by_name(name, a, b)

    def __add__(self, o):
        return self._binop("broadcast_add", o)

    __radd__ = __add__

    def __sub__(self, o):
        return self._binop("broadcast_sub", o)

    def __rsub__(self, o):
        return self._binop("broadcast_sub", o, reverse=True)

    def __mul__(self, o):
        return self._binop("broadcast_mul", o)

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binop("broadcast_div", o)

    def __rtruediv__(self, o):
        return self._binop("broadcast_div", o, reverse=True)

    def __mod__(self, o):
        return self._binop("broadcast_mod", o)

    def __rmod__(self, o):
        return self._binop("broadcast_mod", o, reverse=True)

    def __pow__(self, o):
        return self._binop("broadcast_power", o)

    def __rpow__(self, o):
        return self._binop("broadcast_power", o, reverse=True)

    def __neg__(self):
        from .register import invoke_by_name
        return invoke_by_name("negative", self)

    def __abs__(self):
        from .register import invoke_by_name
        return invoke_by_name("abs", self)

    def _inplace_binop(self, name, o):
        """In-place arithmetic under autograd. The recorded op must consume a
        snapshot ALIAS of the pre-mutation value (carrying the old tape
        position / grad buffer) and the tape node's outputs must point back
        at *this* array — otherwise backward either misses the mutated array
        entirely or sees a self-referential node, and gradients are silently
        zero."""
        alias = NDArray(self._data)
        alias._ag_node, alias._ag_idx = self._ag_node, self._ag_idx
        alias._ag_grad, alias._ag_grad_req = self._ag_grad, self._ag_grad_req
        if alias._ag_node is not None:
            # the alias takes over the old output slot so this array is the
            # output of exactly ONE node (cotangents are keyed by identity)
            alias._ag_node.outputs[alias._ag_idx] = alias
        out = alias._binop(name, o)
        self._data = out._data
        self._ag_node, self._ag_idx = out._ag_node, out._ag_idx
        if self._ag_node is not None:
            self._ag_node.outputs[self._ag_idx] = self
        return self

    def __iadd__(self, o):
        return self._inplace_binop("broadcast_add", o)

    def __isub__(self, o):
        return self._inplace_binop("broadcast_sub", o)

    def __imul__(self, o):
        return self._inplace_binop("broadcast_mul", o)

    def __itruediv__(self, o):
        return self._inplace_binop("broadcast_div", o)

    def __eq__(self, o):
        return self._binop("broadcast_equal", o)

    def __ne__(self, o):
        return self._binop("broadcast_not_equal", o)

    def __gt__(self, o):
        return self._binop("broadcast_greater", o)

    def __ge__(self, o):
        return self._binop("broadcast_greater_equal", o)

    def __lt__(self, o):
        return self._binop("broadcast_lesser", o)

    def __le__(self, o):
        return self._binop("broadcast_lesser_equal", o)

    __hash__ = object.__hash__  # identity hash despite __eq__ override

    def __matmul__(self, o):
        from .register import invoke_by_name
        return invoke_by_name("dot", self, o)

    # ------------------------------------------------------------------ #
    # method sugar delegating to ops
    # ------------------------------------------------------------------ #
    def _op(self, name, *args, **kwargs):
        from .register import invoke_by_name
        return invoke_by_name(name, self, *args, **kwargs)

    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        shape = kwargs.pop("shape", shape)
        return self._op("reshape", shape=tuple(shape), **kwargs)

    def reshape_like(self, other):
        return self._op("reshape_like", other)

    def transpose(self, axes=None):
        return self._op("transpose", axes=axes)

    @property
    def T(self):
        return self.transpose()

    def flatten(self):
        return self._op("flatten")

    def expand_dims(self, axis):
        return self._op("expand_dims", axis=axis)

    def squeeze(self, axis=None):
        return self._op("squeeze", axis=axis)

    def swapaxes(self, dim1, dim2):
        return self._op("swapaxes", dim1=dim1, dim2=dim2)

    def flip(self, axis):
        return self._op("flip", axis=axis)

    def tile(self, reps):
        return self._op("tile", reps=reps)

    def repeat(self, repeats, axis=None):
        return self._op("repeat", repeats=repeats, axis=axis)

    def broadcast_to(self, shape):
        return self._op("broadcast_to", shape=tuple(shape))

    def broadcast_like(self, other):
        return self._op("broadcast_like", other)

    def sum(self, axis=None, keepdims=False):
        return self._op("sum", axis=axis, keepdims=keepdims)

    def mean(self, axis=None, keepdims=False):
        return self._op("mean", axis=axis, keepdims=keepdims)

    def prod(self, axis=None, keepdims=False):
        return self._op("prod", axis=axis, keepdims=keepdims)

    def max(self, axis=None, keepdims=False):
        return self._op("max", axis=axis, keepdims=keepdims)

    def min(self, axis=None, keepdims=False):
        return self._op("min", axis=axis, keepdims=keepdims)

    def norm(self, ord=2, axis=None, keepdims=False):
        return self._op("norm", ord=ord, axis=axis, keepdims=keepdims)

    def argmax(self, axis=None, keepdims=False):
        return self._op("argmax", axis=axis, keepdims=keepdims)

    def argmin(self, axis=None, keepdims=False):
        return self._op("argmin", axis=axis, keepdims=keepdims)

    def argsort(self, axis=-1, is_ascend=True):
        return self._op("argsort", axis=axis, is_ascend=is_ascend)

    def sort(self, axis=-1, is_ascend=True):
        return self._op("sort", axis=axis, is_ascend=is_ascend)

    def topk(self, axis=-1, k=1, ret_typ="indices", is_ascend=False):
        return self._op("topk", axis=axis, k=k, ret_typ=ret_typ,
                        is_ascend=is_ascend)

    def clip(self, a_min, a_max):
        return self._op("clip", a_min=a_min, a_max=a_max)

    def pad(self, mode="constant", pad_width=None, constant_value=0.0):
        return self._op("pad", mode=mode, pad_width=pad_width,
                        constant_value=constant_value)

    def abs(self):
        return self._op("abs")

    def sign(self):
        return self._op("sign")

    def sqrt(self):
        return self._op("sqrt")

    def square(self):
        return self._op("square")

    def exp(self):
        return self._op("exp")

    def log(self):
        return self._op("log")

    def relu(self):
        return self._op("relu")

    def sigmoid(self):
        return self._op("sigmoid")

    def tanh(self):
        return self._op("tanh")

    def softmax(self, axis=-1):
        return self._op("softmax", axis=axis)

    def log_softmax(self, axis=-1):
        return self._op("log_softmax", axis=axis)

    def dot(self, other, transpose_a=False, transpose_b=False):
        return self._op("dot", other, transpose_a=transpose_a,
                        transpose_b=transpose_b)

    def take(self, indices, axis=0, mode="clip"):
        return self._op("take", indices, axis=axis, mode=mode)

    def pick(self, index, axis=-1, keepdims=False):
        return self._op("pick", index, axis=axis, keepdims=keepdims)

    def one_hot(self, depth, on_value=1.0, off_value=0.0):
        return self._op("one_hot", depth=depth, on_value=on_value,
                        off_value=off_value)

    def slice(self, begin, end, step=None):
        return self._op("slice", begin=begin, end=end, step=step)

    def slice_axis(self, axis, begin, end):
        return self._op("slice_axis", axis=axis, begin=begin, end=end)

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return self._op("split", num_outputs=num_outputs, axis=axis,
                        squeeze_axis=squeeze_axis)

    def zeros_like(self):
        return self._op("zeros_like")

    def ones_like(self):
        return self._op("ones_like")
