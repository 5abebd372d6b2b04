"""Device contexts: cpu / gpu / tpu.

Reference: include/mxnet/base.h:102 `struct Context` with DeviceType
{kCPU=1, kGPU=2, kCPUPinned=3, kCPUShared=5} (base.h:105-108) and
python/mxnet/context.py:327 (`cpu()/gpu()/cpu_pinned()`, default-ctx stack).

TPU-native redesign: a Context is a named view onto a `jax.Device`. `tpu()` is
first-class (the reference's north-star `kTPU` device type). Device placement
is realized with `jax.device_put` / sharding rather than per-device storage
managers — XLA owns HBM (reference src/storage/ is subsumed by the XLA
allocator, see SURVEY.md §7 translation table).
"""
from __future__ import annotations

import threading

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "cpu_shared",
           "current_context", "num_gpus", "num_tpus", "gpu_memory_info"]


class DeviceType:
    kCPU = 1
    kGPU = 2
    kCPUPinned = 3
    kCPUShared = 5
    kTPU = 6


_DEVTYPE_NAME = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
_NAME_DEVTYPE = {v: k for k, v in _DEVTYPE_NAME.items()}

# jax platform names that count as each device kind
_TPU_PLATFORMS = ("tpu",)
_GPU_PLATFORMS = ("gpu", "cuda", "rocm")


class _TLS(threading.local):
    def __init__(self):
        super().__init__()
        self.stack = []


_tls = _TLS()


def _jax_devices_for(device_typename: str):
    import jax
    plats = {"tpu": _TPU_PLATFORMS, "gpu": _GPU_PLATFORMS}.get(
        device_typename, (device_typename,))
    # local_devices: under a multi-process (pod) runtime jax.devices() is
    # GLOBAL and placing eager arrays on another process's device is
    # invalid — a Context always names a process-local device (the
    # reference's Context is likewise node-local)
    out = []
    for d in jax.local_devices():
        if d.platform.lower() in plats:
            out.append(d)
    if device_typename == "cpu" and not out:
        # default-backend local_devices may be TPU-only; ask the cpu
        # backend for ITS process-local devices (never the global list —
        # placing eager arrays on another process's device is invalid)
        try:
            out = jax.local_devices(backend="cpu")
        except RuntimeError:
            out = [d for d in jax.devices("cpu")
                   if d.process_index == jax.process_index()] or \
                jax.devices("cpu")
    return out


class Context:
    """Device context. Constructing one never touches hardware; `.jax_device`
    resolves lazily (reference Context is likewise a plain (type, id) pair,
    include/mxnet/base.h:158-167)."""

    devtype2str = _DEVTYPE_NAME
    devstr2type = _NAME_DEVTYPE

    def __init__(self, device_type, device_id: int = 0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_typename, device_type.device_id
        if isinstance(device_type, int):
            device_type = _DEVTYPE_NAME[device_type]
        if device_type not in _NAME_DEVTYPE:
            raise MXNetError(f"unknown device type {device_type!r}")
        self.device_typename = device_type
        self.device_id = int(device_id)

    @property
    def device_type(self):
        return self.device_typename

    @property
    def _base_typename(self):
        # pinned/shared CPU memory distinctions are host-runtime details of the
        # reference (src/storage/storage.cc:62-120); on the JAX runtime they all
        # map to the host platform.
        n = self.device_typename
        return "cpu" if n.startswith("cpu") else n

    @property
    def jax_device(self):
        devs = _jax_devices_for(self._base_typename)
        if not devs:
            raise MXNetError(f"no {self._base_typename} device available "
                             f"(jax sees: {_platforms()})")
        if self.device_id >= len(devs):
            raise MXNetError(f"{self._base_typename}({self.device_id}) out of range; "
                             f"{len(devs)} device(s) present")
        return devs[self.device_id]

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_typename == other.device_typename
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_typename, self.device_id))

    def __repr__(self):
        return f"{self.device_typename}({self.device_id})"

    __str__ = __repr__

    def __enter__(self):
        _tls.stack.append(self)
        return self

    def __exit__(self, *exc):
        _tls.stack.pop()

    @classmethod
    def default_ctx(cls):
        return current_context()


def _platforms():
    import jax
    return sorted({d.platform for d in jax.devices()})


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def cpu_shared(device_id: int = 0) -> Context:
    return Context("cpu_shared", device_id)


def gpu(device_id: int = 0) -> Context:
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    """First-class TPU context — the north star of the port
    (reference: BASELINE.json north_star; include/mxnet/base.h would gain kTPU)."""
    return Context("tpu", device_id)


def num_gpus() -> int:
    return len(_jax_devices_for("gpu"))


def num_tpus() -> int:
    return len(_jax_devices_for("tpu"))


def gpu_memory_info(device_id: int = 0):
    """(free, total) bytes; reference python/mxnet/context.py mx.context.gpu_memory_info.
    On TPU/JAX runtimes memory stats come from device.memory_stats()."""
    for name in ("gpu", "tpu"):
        devs = _jax_devices_for(name)
        if devs and device_id < len(devs):
            stats = devs[device_id].memory_stats() or {}
            total = stats.get("bytes_limit", 0)
            used = stats.get("bytes_in_use", 0)
            return (total - used, total)
    raise MXNetError("no accelerator device")


def current_context() -> Context:
    """Default context, settable via `with mx.tpu(0):` (reference
    python/mxnet/context.py:327 default-ctx stack). Out of the box it prefers
    the best available device: tpu > gpu > cpu."""
    if _tls.stack:
        return _tls.stack[-1]
    return _best_context()


_best_cache = None


def _best_context() -> Context:
    global _best_cache
    if _best_cache is None:
        if num_tpus():
            _best_cache = tpu(0)
        elif num_gpus():
            _best_cache = gpu(0)
        else:
            _best_cache = cpu(0)
    return _best_cache
