"""NDArray save/load over the reference dmlc binary container.

Reference: python/mxnet/ndarray/utils.py:149 save/load over the dmlc::Stream
binary container (MXNDArraySave, include/mxnet/c_api.h:656; impl
src/ndarray/ndarray.cc:1594-1781). The container stores either a list or a
str->NDArray map:

    uint64 kMXAPINDArrayListMagic (0x112)
    uint64 reserved (0)
    vector<NDArray>   -- uint64 count, then NDArray::Save per element
    vector<string>    -- uint64 count, then (uint64 len + bytes) per name

Each dense NDArray (NDArray::Save, src/ndarray/ndarray.cc):

    uint32 NDARRAY_V2_MAGIC (0xF993FAC9)       V3 = np-shape semantics
    int32  storage type (0 dense / 1 row_sparse / 2 csr)
    [sparse only] storage shape: uint32 ndim + int64 dims
    shape: uint32 ndim + int64 dims             (uint32 dims in legacy v0)
    int32 dev_type, int32 dev_id                (Context::Save; cpu = 1)
    int32 type flag (mshadow: 0=f32 1=f64 2=f16 3=u8 4=i32 5=i8 6=i64)
    [sparse only] per aux: int32 type flag + shape
    raw data bytes (C order), then raw aux bytes

`load` also accepts the three historical layouts the reference reads:
V1 (int64 TShape, no storage type), legacy v0 (the magic field IS ndim and
dims are uint32 — tests/python/unittest/legacy_ndarray.v0), and this repo's
pre-wire .npz container. `save` always writes the dmlc wire so exported
`.params` are loadable by reference-compatible consumers (c_predict, the
serve/ Predictor, other frontends).
"""
from __future__ import annotations

import os
import struct

import numpy as _np

from ..base import MXNetError
from .ndarray import NDArray
from .sparse import CSRNDArray, RowSparseNDArray

__all__ = ["save", "save_bytes", "load", "load_frombuffer", "from_dlpack",
           "to_dlpack_for_read", "to_dlpack_for_write"]

# legacy npz container keys (pre-wire format; load-only)
_MAGIC_KEY = "__mxtpu_ndarray_container__"
_LIST_PREFIX = "__list__:"

_ND_LIST_MAGIC = 0x112            # kMXAPINDArrayListMagic, c_api.cc
_NDARRAY_V1_MAGIC = 0xF993FAC8    # int64 TShape
_NDARRAY_V2_MAGIC = 0xF993FAC9    # + storage type
_NDARRAY_V3_MAGIC = 0xF993FACA    # np-shape semantics (0-dim allowed)
_V3_NONE_NDIM = 0xFFFFFFFF        # np-shape "unknown" ndim (-1 as uint32)

_STYPE_DEFAULT, _STYPE_ROW_SPARSE, _STYPE_CSR = 0, 1, 2
_NUM_AUX = {_STYPE_DEFAULT: 0, _STYPE_ROW_SPARSE: 1, _STYPE_CSR: 2}
_DEV_CPU = 1                      # Context::DeviceType kCPU


def _bfloat16():
    import ml_dtypes
    return ml_dtypes.bfloat16


def _type_flag(dtype):
    """numpy/jax dtype -> mshadow type flag (mshadow/base.h)."""
    name = _np.dtype(dtype).name if "bfloat16" not in str(dtype) else "bfloat16"
    flags = {"float32": 0, "float64": 1, "float16": 2, "uint8": 3,
             "int32": 4, "int8": 5, "int64": 6, "bool": 7, "bfloat16": 12}
    if name not in flags:
        raise MXNetError(f"dtype {dtype} has no mshadow type flag")
    return flags[name]


def _np_dtype(flag):
    table = {0: _np.float32, 1: _np.float64, 2: _np.float16, 3: _np.uint8,
             4: _np.int32, 5: _np.int8, 6: _np.int64, 7: _np.bool_}
    if flag in table:
        return _np.dtype(table[flag])
    if flag == 12:
        return _np.dtype(_bfloat16())
    raise MXNetError(f"unknown mshadow type flag {flag}")


# ---------------------------------------------------------------------------
# writer
# ---------------------------------------------------------------------------

def _write_shape(out, shape):
    out.append(struct.pack("<I", len(shape)))
    if shape:
        out.append(struct.pack(f"<{len(shape)}q", *shape))


def _raw_bytes(arr):
    host = arr.asnumpy() if isinstance(arr, NDArray) else _np.asarray(arr)
    return _np.ascontiguousarray(host).tobytes()


def _save_one(out, arr):
    if isinstance(arr, RowSparseNDArray):
        stype, aux = _STYPE_ROW_SPARSE, [arr.indices]
        storage_shape = tuple(arr.data.shape)
        data = arr.data
    elif isinstance(arr, CSRNDArray):
        stype, aux = _STYPE_CSR, [arr.indptr, arr.indices]
        storage_shape = tuple(arr.data.shape)
        data = arr.data
    elif isinstance(arr, NDArray):
        stype, aux, storage_shape, data = _STYPE_DEFAULT, [], None, arr
    else:
        raise MXNetError(f"save expects NDArrays, got {type(arr)}")
    shape = tuple(arr.shape)
    # pre-np TShape cannot express a 0-dim scalar: those go on the V3 wire
    magic = _NDARRAY_V3_MAGIC if len(shape) == 0 else _NDARRAY_V2_MAGIC
    out.append(struct.pack("<Ii", magic, stype))
    if storage_shape is not None:
        _write_shape(out, storage_shape)
    _write_shape(out, shape)
    out.append(struct.pack("<ii", _DEV_CPU, 0))
    out.append(struct.pack("<i", _type_flag(data.dtype)))
    # reference sparse aux index dtype is int64 (ROW_SPARSE_IDX_TYPE)
    for a in aux:
        out.append(struct.pack("<i", _type_flag(_np.int64)))
        _write_shape(out, tuple(a.shape))
    out.append(_raw_bytes(data))
    for a in aux:
        out.append(_raw_bytes(_np.asarray(a.asnumpy(), _np.int64)))


def save_bytes(data):
    """Serialize a list or dict of NDArrays to the reference dmlc binary
    wire and return the bytes (what :func:`save` writes). Callers that
    need the payload in memory anyway (checksummed checkpoints) avoid a
    write-then-read-back round trip."""
    if isinstance(data, (NDArray, RowSparseNDArray, CSRNDArray)):
        data = [data]
    if isinstance(data, (list, tuple)):
        names, arrays = [], list(data)
    elif isinstance(data, dict):
        names, arrays = list(data.keys()), list(data.values())
        if not all(isinstance(k, str) for k in names):
            raise MXNetError("save expects str keys")
    else:
        raise MXNetError(f"cannot save {type(data)}")
    out = [struct.pack("<QQ", _ND_LIST_MAGIC, 0),
           struct.pack("<Q", len(arrays))]
    for a in arrays:
        _save_one(out, a)
    out.append(struct.pack("<Q", len(names)))
    for n in names:
        raw = n.encode("utf-8")
        out.append(struct.pack("<Q", len(raw)))
        out.append(raw)
    return b"".join(out)


def save(fname: str, data):
    """Save a list or dict of NDArrays on the reference dmlc binary wire
    (reference ndarray/utils.py save -> MXNDArraySave)."""
    payload = save_bytes(data)
    with open(fname, "wb") as f:
        f.write(payload)


# ---------------------------------------------------------------------------
# reader
# ---------------------------------------------------------------------------

class _Reader:
    """Little-endian cursor over the container bytes; every read is
    bounds-checked so a truncated file raises MXNetError, not a slice
    of garbage."""

    def __init__(self, buf):
        self._buf = memoryview(buf)
        self._pos = 0

    def bytes(self, n):
        if self._pos + n > len(self._buf):
            raise MXNetError(
                f"truncated NDArray container (wanted {n} bytes at offset "
                f"{self._pos}, have {len(self._buf)})")
        out = self._buf[self._pos:self._pos + n]
        self._pos += n
        return out

    def unpack(self, fmt):
        vals = struct.unpack("<" + fmt, self.bytes(struct.calcsize("<" + fmt)))
        return vals[0] if len(vals) == 1 else vals

    def shape(self, legacy_u32=False, ndim=None):
        if ndim is None:
            ndim = self.unpack("I")
        if ndim == _V3_NONE_NDIM:
            return None
        fmt = "I" if legacy_u32 else "q"
        if not ndim:
            return ()
        vals = self.unpack(f"{ndim}{fmt}")
        return tuple(vals) if isinstance(vals, tuple) else (vals,)

    def array(self, shape, dtype):
        n = int(_np.prod(shape, dtype=_np.int64)) if shape else 1
        raw = self.bytes(n * dtype.itemsize)
        return _np.frombuffer(raw, dtype=dtype).reshape(shape).copy()


def _load_one(r: _Reader):
    """One NDArray entry (reference NDArray::Load + LegacyLoad)."""
    magic = r.unpack("I")
    if magic in (_NDARRAY_V2_MAGIC, _NDARRAY_V3_MAGIC):
        stype = r.unpack("i")
        if stype not in _NUM_AUX:
            raise MXNetError(f"unknown storage type {stype} in container")
        nad = _NUM_AUX[stype]
        storage_shape = r.shape() if nad > 0 else None
        shape = r.shape()
        if shape is None or (magic == _NDARRAY_V2_MAGIC and shape == ()):
            # reference: shape_is_none -> default (empty) NDArray, and
            # Save stopped right after the shape for those
            return NDArray(_np.zeros((0,), _np.float32))
        r.unpack("ii")  # context (dev_type, dev_id) — always loaded to host
        dtype = _np_dtype(r.unpack("i"))
        aux_dtypes, aux_shapes = [], []
        for _ in range(nad):
            aux_dtypes.append(_np_dtype(r.unpack("i")))
            aux_shapes.append(r.shape())
        data = r.array(storage_shape if nad else shape, dtype)
        aux = [r.array(s, d) for d, s in zip(aux_dtypes, aux_shapes)]
        if stype == _STYPE_ROW_SPARSE:
            return RowSparseNDArray(data, aux[0], shape)
        if stype == _STYPE_CSR:
            return CSRNDArray(data, aux[1], aux[0], shape)
        return NDArray(data)
    # V1 (int64 dims) or legacy v0 (magic field IS ndim, uint32 dims)
    if magic == _NDARRAY_V1_MAGIC:
        shape = r.shape()
    else:
        shape = r.shape(legacy_u32=True, ndim=magic)
    if shape == ():
        return NDArray(_np.zeros((0,), _np.float32))
    r.unpack("ii")  # context
    dtype = _np_dtype(r.unpack("i"))
    return NDArray(r.array(shape, dtype))


def load_frombuffer(buf):
    """Load a container from bytes (reference ndarray/utils.py
    load_frombuffer -> MXNDArrayLoadFromBuffer) — the c_predict_api takes
    the .params payload this way."""
    if isinstance(buf, memoryview):
        buf = bytes(buf)
    if not isinstance(buf, (bytes, bytearray)):
        raise MXNetError("load_frombuffer expects bytes")
    r = _Reader(buf)
    header, _reserved = r.unpack("QQ")
    if header != _ND_LIST_MAGIC:
        raise MXNetError(
            f"invalid NDArray container magic {header:#x} "
            f"(expected {_ND_LIST_MAGIC:#x})")
    arrays = [_load_one(r) for _ in range(r.unpack("Q"))]
    names = []
    for _ in range(r.unpack("Q")):
        names.append(bytes(r.bytes(r.unpack("Q"))).decode("utf-8"))
    if not names:
        return arrays
    if len(names) != len(arrays):
        raise MXNetError(
            f"container has {len(arrays)} arrays but {len(names)} names")
    return dict(zip(names, arrays))


def load(fname: str):
    """Load a `save` container (reference ndarray/utils.py load). Sniffs
    the legacy .npz layout this repo wrote before the dmlc wire landed."""
    if not os.path.exists(fname):
        raise MXNetError(f"no such file: {fname}")
    with open(fname, "rb") as f:
        payload = f.read()
    if payload[:4] in (b"PK\x03\x04", b"PK\x05\x06"):
        return _load_npz(fname)
    return load_frombuffer(payload)


def _load_npz(fname):
    with _np.load(fname, allow_pickle=False) as z:
        keys = [k for k in z.files if k != _MAGIC_KEY]
        if keys and all(k.startswith(_LIST_PREFIX) for k in keys):
            return [NDArray(z[k]) for k in sorted(keys)]
        return {k: NDArray(z[k]) for k in keys}


# ---------------------------------------------------------------------------
# DLPack interchange (reference MXNDArrayToDLPack/MXNDArrayFromDLPack,
# include/mxnet/c_api.h; python mxnet.ndarray to_dlpack_for_read/
# to_dlpack_for_write/from_dlpack). jax.Array speaks the dlpack protocol
# natively, so these are thin shims kept for API parity — they are the
# zero-copy bridge to torch/cupy/numpy consumers.
# ---------------------------------------------------------------------------

def from_dlpack(ext):
    """Wrap any object exporting __dlpack__ (torch tensor, numpy array,
    another framework's array) as an NDArray, zero-copy when the producer
    is on a compatible device."""
    import jax.numpy as jnp
    return NDArray(jnp.from_dlpack(ext))


def to_dlpack_for_read(arr):
    """Export an NDArray as a DLPack capsule (read intent; XLA arrays are
    immutable so read/write intent coincide — both names kept for parity).
    A backend that cannot export its buffers raises: a silent copy through
    the host would hide which device the consumer ends up reading."""
    return arr._data.__dlpack__()


def to_dlpack_for_write(arr):
    """See to_dlpack_for_read — XLA buffers are immutable; a consumer that
    mutates must copy (the reference's write capsule relied on the engine
    write-var lock, which has no XLA analog)."""
    return to_dlpack_for_read(arr)
