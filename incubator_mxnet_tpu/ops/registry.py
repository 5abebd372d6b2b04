"""Operator registry + eager dispatch.

Reference: NNVM op registry (`NNVM_REGISTER_OP`, 338 registrations in
src/operator/) with typed attributes FInferShape/FInferType/FCompute/FGradient
(include/mxnet/op_attr_types.h), dispatched by Imperative::Invoke
(src/imperative/imperative.cc:89) through the ThreadedEngine.

TPU-native redesign: an op is ONE pure jax function (`fn(*arrays, **params)`)
— shape/dtype inference comes free from `jax.eval_shape` (no separate
FInferShape), the gradient comes free from `jax.vjp` (no hand-written
`_backward_*` ops), and the "engine" is XLA async dispatch (jax.Array data
dependencies replace the reference's var version chains). Each eager call is
routed through a cached `jax.jit` specialization keyed on (op, shapes,
dtypes, params) so steady-state eager dispatch stays on the fast path — the
moral equivalent of the reference's CachedOp op-bulking without the graph.
"""
from __future__ import annotations

import functools
import weakref

from .. import autograd
from ..base import MXNetError, Registry

__all__ = ["OpDef", "register", "get_op", "invoke", "OPS", "apply_op"]

OPS = Registry("operator")

# AMP dispatch hook (contrib/amp/amp.py): fn(op_name, arr_list, params) ->
# arr_list, applied to unwrapped jax arrays before dispatch. The reference
# instead monkey-patches every generated op wrapper (contrib/amp/amp.py:
# 48-140); here ONE choke point covers eager, hybridized, and symbolic
# execution.
AMP_HOOK = None

# Profiler dispatch hook (profiler.py): fn(op_name, callable, args) -> out,
# times eager op dispatch (the reference wraps engine-op execution,
# src/profiler/profiler.h:251).
PROFILER_HOOK = None


def _match_ct_dtypes(cts, out):
    """Cast cotangents to the primal outputs' dtypes — under AMP a bf16
    op output can receive an fp32 cotangent from a downstream fp32 op."""
    import jax.numpy as jnp

    def _one(ct, o):
        if hasattr(ct, "dtype") and hasattr(o, "dtype") and ct.dtype != o.dtype:
            return ct.astype(o.dtype)
        return ct

    if isinstance(out, (tuple, list)):
        return tuple(_one(c, o) for c, o in zip(cts, out))
    return _one(cts, out)


def _hashable(v):
    if isinstance(v, (list,)):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    return v


class OpDef:
    """One registered operator.

    fn: pure function of jax arrays (positional) + python params (keyword),
    returning one array or a tuple. `stateful=True` ops (random samplers,
    dropout) additionally take a `rng` keyword PRNG key.
    """

    def __init__(self, name, fn, aliases=(), stateful=False, nondiff=False,
                 train_aware=False, eager_only=False):
        self.name = name
        self.fn = fn
        self.aliases = aliases
        self.stateful = stateful
        self.nondiff = nondiff
        # eager_only: dynamic output shape (boolean_mask) — never jit; XLA
        # needs static shapes, so these run op-by-op with concrete inputs
        self.eager_only = eager_only
        # train_aware ops (BatchNorm, Dropout) get `training=` injected from the
        # autograd train-mode flag when the caller didn't pass it — mirrors the
        # reference's ctx.is_train threading (include/mxnet/op_attr_types.h
        # OpContext::is_train).
        self.train_aware = train_aware
        # bounded FIFO: params may embed user callables (control-flow
        # bodies) whose identity changes per call-site — an unbounded dict
        # would leak every compiled executable + captured closure
        self._jit_cache = {}
        self._jit_cache_max = 256

    def vjp_jitted(self, **params):
        """Cached jitted backward: (cts, *primals) -> input cotangents.

        Recomputes the forward inside the executable (rematerialization) so
        the whole fwd+bwd pair is compiled ONCE per (op, params, shapes) and
        reused every step — the reference's analog is the cached `_backward_*`
        op + autotuned kernel; a fresh jax.vjp per call would recompile the
        linearized program every training step.
        """
        import jax
        key = ("vjp", _hashable(params))
        f = self._jit_cache.get(key)
        if f is None:
            if self.stateful:
                def fwd(rng, *xs, _p=params):
                    return self.fn(*xs, rng=rng, **_p)
            else:
                def fwd(*xs, _p=params):
                    return self.fn(*xs, **_p)

            def bwd(cts, *primals):
                out, vjp_fn = jax.vjp(fwd, *primals)
                return vjp_fn(_match_ct_dtypes(cts, out))

            # two-tier executable cache: reports hit/disk-hit/retrace to
            # the profiler's jit tracker and AOT-persists the executable
            from .. import compile_cache as _cc
            f = _cc.cached_jit(f"op:{self.name}:vjp", bwd)
            self._cache_put(key, f)
        return f

    def _cache_put(self, key, f):
        if len(self._jit_cache) >= self._jit_cache_max:
            self._jit_cache.pop(next(iter(self._jit_cache)))
        self._jit_cache[key] = f

    def jitted(self, **params):
        """A jax.jit specialization of this op for the given params.

        Stateful ops receive the PRNG key as a traced leading argument so the
        jit cache is keyed on params only, never on key values.
        """
        key = _hashable(params)
        f = self._jit_cache.get(key)
        if f is None:
            # two-tier executable cache: every call through it reports
            # hit/disk-hit/recompile to the profiler's jit tracker, and the
            # compiled executable persists across processes when
            # MXNET_EXEC_CACHE_DIR is set
            from .. import compile_cache as _cc
            if self.stateful:
                base = self.fn

                def f_rng(rng, *arrs, _base=base, _params=params):
                    return _base(*arrs, rng=rng, **_params)

                f = _cc.cached_jit(f"op:{self.name}", f_rng)
            else:
                f = _cc.cached_jit(f"op:{self.name}",
                                   functools.partial(self.fn, **params))
            self._cache_put(key, f)
        return f

    def __call__(self, *args, **kwargs):
        return apply_op(self, *args, **kwargs)

    def __repr__(self):
        return f"<Op {self.name}>"


def register(name=None, aliases=(), stateful=False, nondiff=False, train_aware=False,
             eager_only=False):
    """Decorator: @register() on `def op_name(x, y, *, param): ...`."""

    def _do(fn):
        opname = name or fn.__name__
        op = OpDef(opname, fn, aliases=aliases, stateful=stateful, nondiff=nondiff,
                   train_aware=train_aware, eager_only=eager_only)
        OPS.register(op, name=opname, aliases=aliases)
        return op

    return _do


def get_op(name) -> OpDef:
    return OPS.get(name)


def _wrap_out(x, like=None):
    from ..ndarray import NDArray
    return NDArray(x)


def apply_op(op: OpDef, *args, out=None, **params):
    """Eager invoke: unwrap NDArrays -> run jax fn -> wrap outputs -> record tape.

    Reference call path: MXImperativeInvokeEx (src/c_api/c_api_ndarray.cc:132)
    -> Imperative::Invoke (imperative.cc:89) -> PushFCompute
    (imperative_utils.h:394) -> Engine::PushAsync. Here the whole path is one
    cached-jit call; XLA's async runtime gives the same compute/dispatch overlap.
    """
    import jax
    from ..ndarray import NDArray

    arrs = []
    nd_inputs = []
    for a in args:
        if isinstance(a, NDArray):
            nd_inputs.append(a)
            arrs.append(a._data)
        else:
            arrs.append(a)

    if AMP_HOOK is not None:
        arrs = AMP_HOOK(op.name, arrs, params)

    if op.train_aware and params.get("training") is None:
        params = dict(params)
        params["training"] = autograd.is_training()

    if op.stateful:
        from ..ndarray import random as _rnd
        rng = params.pop("rng", None)
        if rng is None:
            rng = _rnd.next_key()
        arrs = [rng] + arrs

    recording = autograd.is_recording() and not op.nondiff

    # Inside an outer trace (hybridize / pjit train step) call the raw fn:
    # nested jit would both block some vjp rules (reduce_window) and prevent
    # whole-graph fusion. Eagerly, the jit-cached specialization is the fast
    # dispatch path (reference: engine op bulking, graph_executor.cc:1288).
    import jax.core as _core
    traced = any(isinstance(a, _core.Tracer) for a in arrs)
    if traced or op.eager_only:
        if op.stateful:
            fn = lambda rng, *xs, _p=params: op.fn(*xs, rng=rng, **_p)
        else:
            fn = lambda *xs, _p=params: op.fn(*xs, **_p)
        if traced:
            # the op's registry name on every instruction it lowers to (and,
            # through jax's own jvp/transpose wrappers, on its backward):
            # acts while the outer program is traced, costs nothing per step
            fn = jax.named_scope(op.name)(fn)
    else:
        fn = op.jitted(**params)

    bwd_info = None
    if recording and traced:
        # inside an outer trace the vjp is part of that trace; no caching issue
        out_data, _raw_vjp = jax.vjp(fn, *arrs)
        vjp_fn = lambda cts, _v=_raw_vjp, _o=out_data: \
            _v(_match_ct_dtypes(cts, _o))
    elif recording and op.eager_only:
        # dynamic-shape op: the jit-cached vjp would re-trace op.fn with
        # abstract inputs, defeating eager_only. Differentiate only arg 0
        # (data); the rest (masks/indices) stay concrete python values so
        # op.fn can inspect them, and get zero cotangents.
        rest = tuple(arrs[1:])
        out_data, _raw_vjp = jax.vjp(
            lambda d, _r=rest, _p=params: op.fn(d, *_r, **_p), arrs[0])

        def vjp_fn(cts, _v=_raw_vjp, _o=out_data, _r=rest):
            gd = _v(_match_ct_dtypes(cts, _o))
            import jax.numpy as _jnp
            return (gd[0],) + tuple(_jnp.zeros_like(r) for r in _r)
    else:
        if PROFILER_HOOK is not None and not traced:
            out_data = PROFILER_HOOK(op.name, fn, arrs)
        else:
            out_data = fn(*arrs)
        vjp_fn = None
        if recording:
            # deferred, jit-cached backward (recomputes forward in-executable)
            bwd = op.vjp_jitted(**params)
            saved = list(arrs)
            vjp_fn = lambda cts, _b=bwd, _s=saved: _b(cts, *_s)
            bwd_info = (op, dict(params), saved)

    multi = isinstance(out_data, (tuple, list))
    # Class-preserving wrap: an mxnet.numpy ndarray input propagates its
    # class through every op (the reference instead duplicates the whole op
    # surface as _np_* registrations, src/operator/numpy/).
    out_cls = type(nd_inputs[0]) if nd_inputs else NDArray
    outs = [out_cls(o) for o in (out_data if multi else (out_data,))]

    if recording:
        off = 1 if op.stateful else 0
        ndarray_positions = [i + off for i, a in enumerate(args) if isinstance(a, NDArray)]

        def node_vjp(cts):
            gin = vjp_fn(cts)
            return tuple(gin[i] for i in ndarray_positions)

        node = autograd.Node(node_vjp, nd_inputs, op.name)
        node.out_refs = [weakref.ref(o) for o in outs]
        node.out_avals = [(o.shape, o.dtype) for o in outs]
        # create_graph (higher-order) support: enough context to replay
        # this node's backward as a RECORDED op (autograd._record_bwd)
        if bwd_info is not None:
            node.bwd_info = (bwd_info[0], bwd_info[1], bwd_info[2],
                             list(ndarray_positions))
        for o in outs:
            o._ag_node = node

    if out is not None:
        tgt = out if isinstance(out, (tuple, list)) else (out,)
        for t, o in zip(tgt, outs):
            t._data = o._data
            t._ag_node = getattr(o, "_ag_node", None)
        return out
    if multi:
        return outs
    return outs[0]


def invoke(name, *args, **kwargs):
    return apply_op(get_op(name), *args, **kwargs)
