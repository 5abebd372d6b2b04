"""NDArray: the framework's tensor type, backed by jax.Array.

Reference: include/mxnet/ndarray.h:82 `class NDArray` + src/ndarray/ndarray.cc
(ref-counted async tensor whose every op is pushed to the dependency engine)
and python/mxnet/ndarray/ndarray.py (user API: indexing, asnumpy, copyto,
autograd attrs, arithmetic dunders).

TPU-native redesign: jax.Array is ALREADY an async, device-resident,
sharding-aware tensor — the reference's engine-var machinery (WaitToRead
ndarray.h:368) maps to `block_until_ready`, and cross-device copy maps to
`jax.device_put`. Mutation semantics (`a[:] = x`, in-place ops) are realized
by swapping the underlying immutable jax buffer, which preserves MXNet's user
model while keeping every actual computation functional for XLA.
"""
from __future__ import annotations

import numpy as _np

from .. import autograd
from ..base import MXNetError, dtype_np
from ..context import Context, current_context

__all__ = ["NDArray", "array", "zeros", "ones", "full", "arange", "empty",
           "concatenate", "moveaxis", "waitall", "from_jax", "linspace", "eye"]


def _jnp():
    import jax.numpy as jnp
    return jnp


# Memory-profiler hook (profiler.py): fn(jax_array) accounting one device
# buffer. Installed only while `profiler.set_config(profile_memory=True)`
# is active, None otherwise — NDArray construction is the choke point every
# eager op output and user array crosses (the reference instead hooks
# StorageManager::Alloc, src/profiler/storage_profiler.h).
MEMORY_HOOK = None


class NDArray:
    """n-dimensional array on a device (cpu/gpu/tpu)."""

    __slots__ = ("_data", "_grad", "_grad_req", "_ag_node", "__weakref__")

    def __init__(self, data, ctx: Context | None = None, dtype=None):
        import jax
        jnp = _jnp()
        if isinstance(data, NDArray):
            data = data._data
        if not hasattr(data, "dtype") or isinstance(data, (_np.ndarray, _np.generic)):
            data = jnp.asarray(data, dtype=dtype_np(dtype) if dtype else None)
        elif dtype is not None:
            data = jnp.asarray(data, dtype=dtype_np(dtype))
        if ctx is not None and not _is_tracer(data):
            data = jax.device_put(data, ctx.jax_device)
        self._data = data
        self._grad = None
        self._grad_req = "null"
        self._ag_node = None
        if MEMORY_HOOK is not None and not _is_tracer(data):
            MEMORY_HOOK(data)

    # ---- basic properties -------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def size(self):
        s = 1
        for d in self.shape:
            s *= d
        return s

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def context(self) -> Context:
        try:
            dev = next(iter(self._data.devices()))
        except Exception:
            return current_context()
        plat = dev.platform.lower()
        if plat == "tpu":
            return Context("tpu", dev.id)
        if plat in ("gpu", "cuda", "rocm"):
            return Context("gpu", dev.id)
        return Context("cpu", dev.id)

    ctx = context

    @property
    def grad(self):
        return self._grad

    @property
    def T(self):
        from .. import nd
        return nd.transpose(self)

    # ---- sync / host transfer --------------------------------------------
    def wait_to_read(self):
        """Reference include/mxnet/ndarray.h:368 WaitToRead."""
        if not _is_tracer(self._data):
            self._data.block_until_ready()
        return self

    wait_to_write = wait_to_read

    def asnumpy(self) -> _np.ndarray:
        """Blocking copy to host (reference python/mxnet/ndarray/ndarray.py asnumpy)."""
        return _np.asarray(self._data)

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def asjax(self):
        """Zero-copy view of the underlying jax.Array (dlpack analog:
        reference MXNDArrayToDLPack, include/mxnet/c_api.h)."""
        return self._data

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    def __dlpack__(self, **kw):
        return self._data.__dlpack__(**kw)

    # ---- shape / dtype / device movement ---------------------------------
    def astype(self, dtype, copy=True):
        from .. import nd
        return nd.cast(self, dtype=str(_np.dtype(dtype_np(dtype)).name)
                       if "bfloat16" not in str(dtype) else "bfloat16")

    def reshape(self, *shape, **kwargs):
        from .. import nd
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return nd.reshape(self, shape=shape)

    def reshape_like(self, other):
        return self.reshape(other.shape)

    def expand_dims(self, axis):
        from .. import nd
        return nd.expand_dims(self, axis=axis)

    def transpose(self, axes=None):
        from .. import nd
        return nd.transpose(self, axes=axes)

    def flatten(self):
        from .. import nd
        return nd.flatten(self)

    def squeeze(self, axis=None):
        from .. import nd
        return nd.squeeze(self, axis=axis)

    def broadcast_to(self, shape):
        from .. import nd
        return nd.broadcast_to(self, shape=tuple(shape))

    def as_in_context(self, ctx: Context):
        """Reference python/mxnet/ndarray/ndarray.py as_in_context; copy only
        when crossing devices (CopyFromTo, src/ndarray/ndarray.cc)."""
        if ctx == self.context:
            return self
        return self.copyto(ctx)

    as_in_ctx = as_in_context

    def copyto(self, other):
        import jax
        if isinstance(other, Context):
            return NDArray(jax.device_put(self._data, other.jax_device))
        if isinstance(other, NDArray):
            other._data = jax.device_put(self._data, other.context.jax_device)
            return other
        raise MXNetError(f"copyto: unsupported target {type(other)}")

    def copy(self):
        return NDArray(self._data + 0 if self.dtype != _np.bool_ else self._data)

    def detach(self):
        out = NDArray(self._data)
        return out

    def tolist(self):
        return self.asnumpy().tolist()

    # ---- autograd ---------------------------------------------------------
    def attach_grad(self, grad_req="write", stype=None):
        """Reference python/mxnet/ndarray/ndarray.py attach_grad. With
        stype='row_sparse' the grad buffer starts as an empty row-sparse
        array (Embedding sparse_grad path)."""
        if stype == "row_sparse":
            from .sparse import zeros as sparse_zeros
            self._grad = sparse_zeros("row_sparse", self.shape,
                                      dtype=self.dtype)
        else:
            jnp = _jnp()
            self._grad = NDArray(jnp.zeros(self.shape, self.dtype))
        self._grad_req = grad_req

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    def zero_grad(self):
        if self._grad is not None:
            jnp = _jnp()
            if getattr(self._grad, "stype", "default") != "default":
                # a row_sparse grad buffer resets to a fresh dense zero
                self._grad = NDArray(jnp.zeros(self.shape, self.dtype))
            else:
                self._grad._data = jnp.zeros(self._grad.shape,
                                             self._grad.dtype)

    @property
    def stype(self):
        """Storage type (reference ndarray.h:61-66); dense arrays are
        'default', see ndarray/sparse.py for row_sparse/csr."""
        return "default"

    def tostype(self, stype):
        from .sparse import cast_storage
        return cast_storage(self, stype)

    def as_np_ndarray(self):
        """View as mxnet.numpy ndarray, preserving the autograd tape
        (reference ndarray.py as_np_ndarray)."""
        from ..numpy.multiarray import _rewrap, ndarray as _np_nd
        return _rewrap(_np_nd, self)

    def as_nd_ndarray(self):
        return self

    # ---- indexing ---------------------------------------------------------
    def _index_data(self, key):
        if isinstance(key, NDArray):
            return key._data
        if isinstance(key, tuple):
            return tuple(k._data if isinstance(k, NDArray) else k for k in key)
        return key

    def __getitem__(self, key):
        from ..ops.registry import invoke
        key = self._index_data(key)
        if isinstance(key, (int, _np.integer)) and \
                not isinstance(key, (bool, _np.bool_)) and self.ndim > 0:
            # int index as an operand: one executable for ALL i (the
            # Dataset[i] hot path; a static key would compile per index)
            n = self.shape[0]
            i = int(key) + n if key < 0 else int(key)
            if not 0 <= i < n:
                raise IndexError(f"index {key} out of bounds for axis 0 "
                                 f"with size {n}")
            if i < 2**31:
                import jax.numpy as jnp
                return invoke("_index_axis0", self,
                              NDArray(jnp.asarray(i, jnp.int32)))
            # >2^31: an int32 index operand would overflow (large-tensor
            # audit). The static-key op compiles per index (fine — giant
            # arrays are rare) and, unlike a raw lax call here, goes
            # through invoke() so the autograd tape still records it.
            return invoke("_getitem_static", self, key=_freeze_index(i))
        if _static_index(key):
            return invoke("_getitem_static", self, key=_freeze_index(key))
        # advanced indexing with array keys: route arrays as op inputs is
        # overkill for eager; concretize (documented: not jit-traceable).
        return NDArray(self._data[key])

    def __setitem__(self, key, value):
        key = self._index_data(key)
        if isinstance(value, NDArray):
            value = value._data
        jnp = _jnp()
        if key is Ellipsis or (isinstance(key, slice) and key == slice(None)):
            self._data = jnp.broadcast_to(jnp.asarray(value, self.dtype), self.shape) + \
                jnp.zeros(self.shape, self.dtype)
        else:
            self._data = self._data.at[key].set(value)

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __bool__(self):
        if self.size == 0:
            return False
        if self.size == 1:
            return bool(self.asscalar())
        raise MXNetError("ambiguous truth value of multi-element NDArray")

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __index__(self):
        return int(self.asscalar())

    def __hash__(self):
        return id(self)

    def __repr__(self):
        try:
            body = str(self.asnumpy())
        except Exception:
            body = f"<traced {self.shape} {self.dtype}>"
        return f"\n{body}\n<NDArray {'x'.join(map(str, self.shape))} @{self.context}>"

    # ---- arithmetic (registry ops so autograd records them) ---------------
    def _binop(self, name, other, reverse=False):
        from ..ops.registry import invoke
        if isinstance(other, NDArray):
            a, b = (other, self) if reverse else (self, other)
            return invoke("broadcast_" + name, a, b)
        scalar = float(other) if not isinstance(other, bool) else other
        return invoke(f"_{'r' if reverse else ''}{name}_scalar", self, scalar=scalar)

    def __add__(self, other):
        return self._binop("add", other)

    def __radd__(self, other):
        return self._binop("add", other, reverse=True)

    def __sub__(self, other):
        return self._binop("sub", other)

    def __rsub__(self, other):
        return self._binop("sub", other, reverse=True)

    def __mul__(self, other):
        return self._binop("mul", other)

    def __rmul__(self, other):
        return self._binop("mul", other, reverse=True)

    def __truediv__(self, other):
        return self._binop("div", other)

    def __rtruediv__(self, other):
        return self._binop("div", other, reverse=True)

    def __mod__(self, other):
        return self._binop("mod", other)

    def __rmod__(self, other):
        return self._binop("mod", other, reverse=True)

    def __pow__(self, other):
        return self._binop("power", other)

    def __rpow__(self, other):
        return self._binop("power", other, reverse=True)

    def __neg__(self):
        from ..ops.registry import invoke
        return invoke("negative", self)

    def __abs__(self):
        from ..ops.registry import invoke
        return invoke("abs", self)

    def __iadd__(self, other):
        res = self.__add__(other)
        self._data, self._ag_node = res._data, res._ag_node
        return self

    def __isub__(self, other):
        res = self.__sub__(other)
        self._data, self._ag_node = res._data, res._ag_node
        return self

    def __imul__(self, other):
        res = self.__mul__(other)
        self._data, self._ag_node = res._data, res._ag_node
        return self

    def __itruediv__(self, other):
        res = self.__truediv__(other)
        self._data, self._ag_node = res._data, res._ag_node
        return self

    def _cmp(self, name, other):
        from ..ops.registry import invoke
        if isinstance(other, NDArray):
            return invoke("broadcast_" + name, self, other)
        return invoke(f"_{name}_scalar", self, scalar=float(other))

    def __eq__(self, other):
        if other is None:
            return False
        return self._cmp("equal", other)

    def __ne__(self, other):
        if other is None:
            return True
        return self._cmp("not_equal", other)

    def __lt__(self, other):
        return self._cmp("lesser", other)

    def __le__(self, other):
        return self._cmp("lesser_equal", other)

    def __gt__(self, other):
        return self._cmp("greater", other)

    def __ge__(self, other):
        return self._cmp("greater_equal", other)

    # ---- reductions as methods -------------------------------------------
    def sum(self, axis=None, keepdims=False):
        from .. import nd
        return nd.sum(self, axis=axis, keepdims=keepdims)

    def mean(self, axis=None, keepdims=False):
        from .. import nd
        return nd.mean(self, axis=axis, keepdims=keepdims)

    def max(self, axis=None, keepdims=False):
        from .. import nd
        return nd.max(self, axis=axis, keepdims=keepdims)

    def min(self, axis=None, keepdims=False):
        from .. import nd
        return nd.min(self, axis=axis, keepdims=keepdims)

    def prod(self, axis=None, keepdims=False):
        from .. import nd
        return nd.prod(self, axis=axis, keepdims=keepdims)

    def argmax(self, axis=None):
        from .. import nd
        return nd.argmax(self, axis=axis)

    def argmin(self, axis=None):
        from .. import nd
        return nd.argmin(self, axis=axis)

    def norm(self):
        from .. import nd
        return nd.norm(self)

    def abs(self):
        return self.__abs__()

    def clip(self, a_min=None, a_max=None):
        from .. import nd
        return nd.clip(self, a_min=a_min, a_max=a_max)

    def slice_axis(self, axis, begin, end):
        from .. import nd
        return nd.slice_axis(self, axis=axis, begin=begin, end=end)

    def take(self, indices, axis=0):
        from .. import nd
        return nd.take(self, indices, axis=axis)

    def dot(self, other):
        from .. import nd
        return nd.dot(self, other)

    def split(self, num_outputs, axis=0):
        from .. import nd
        return nd.split(self, num_outputs=num_outputs, axis=axis)


def _is_tracer(x):
    import jax.core
    return isinstance(x, jax.core.Tracer)


def _static_index(key):
    """True if an index expression contains no device arrays (trace-safe)."""
    if isinstance(key, tuple):
        return all(_static_index(k) for k in key)
    return isinstance(key, (int, slice, type(None), type(Ellipsis), bool))


def _freeze_index(key):
    if isinstance(key, tuple):
        return tuple(_freeze_index(k) for k in key)
    if isinstance(key, slice):
        return ("slice", key.start, key.stop, key.step)
    return key


# ---- factory functions ----------------------------------------------------

def array(obj, ctx=None, dtype=None):
    """Create an NDArray from any array-like. MXNet semantics: python
    lists/scalars become float32 regardless of element type; numpy arrays keep
    their dtype (reference python/mxnet/ndarray/utils.py array, ndarray.py:2506)."""
    if dtype is None and isinstance(obj, (list, tuple, int, float)):
        dtype = "float32"
    return NDArray(obj, ctx=ctx or current_context(), dtype=dtype)


def from_jax(x):
    return NDArray(x)


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=None, **kw):
    from ..ops.registry import invoke
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    out = invoke("_zeros", shape=shape, dtype=str(dtype or "float32"))
    return out if ctx is None else NDArray(out._data, ctx=ctx)


def ones(shape, ctx=None, dtype=None, **kw):
    from ..ops.registry import invoke
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    out = invoke("_ones", shape=shape, dtype=str(dtype or "float32"))
    return out if ctx is None else NDArray(out._data, ctx=ctx)


def full(shape, val, ctx=None, dtype=None):
    from ..ops.registry import invoke
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return invoke("_full", shape=shape, value=float(val), dtype=str(dtype or "float32"))


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    from ..ops.registry import invoke
    return invoke("_arange", start=float(start),
                  stop=None if stop is None else float(stop),
                  step=float(step), repeat=int(repeat), dtype=str(dtype or "float32"))


def linspace(start, stop, num, endpoint=True, ctx=None, dtype=None):
    jnp = _jnp()
    return NDArray(jnp.linspace(start, stop, num, endpoint=endpoint,
                                dtype=dtype_np(dtype or "float32")), ctx=ctx)


def eye(N, M=0, k=0, ctx=None, dtype=None):
    jnp = _jnp()
    return NDArray(jnp.eye(N, M if M else None, k=k, dtype=dtype_np(dtype or "float32")), ctx=ctx)


def concatenate(arrays, axis=0):
    from .. import nd
    return nd.concat(*arrays, dim=axis)


def moveaxis(tensor, source, destination):
    jnp = _jnp()
    return NDArray(jnp.moveaxis(tensor._data, source, destination))


def waitall():
    """Block until all pending async work completes (reference MXNDArrayWaitAll,
    src/c_api/c_api.cc; engine WaitForAll threaded_engine.cc:416)."""
    import jax
    (jax.device_put(0.0) + 0).block_until_ready()
