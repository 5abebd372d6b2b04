"""Functionalize a Gluon block: (params pytree, pure apply fn).

This is the bridge from the imperative Gluon API to pjit-able SPMD programs —
the role GraphExecutor::Init plays in the reference (src/executor/
graph_executor.cc:388: bind a symbolic graph + arrays into an executable),
re-imagined: the "graph" is a traced jax function, the "arrays" a params
pytree keyed by parameter name.
"""
from __future__ import annotations

from collections import OrderedDict

from .. import autograd
from ..ndarray import random as _rnd
from ..ndarray.ndarray import NDArray

__all__ = ["functionalize"]


def functionalize(net, example_inputs, training=True):
    """Returns (params: OrderedDict[str, jax.Array], apply_fn).

    apply_fn(params, rng, *input_arrays) -> (outputs_pytree, state_updates)
    is pure/traceable; state_updates maps param name -> new value (BatchNorm
    running stats) to be applied between steps (or folded into params by the
    caller's train step).
    """
    from ..gluon.block import _StateWriteScope, _TraceScope, _flatten_outputs

    inputs_nd = [x if isinstance(x, NDArray) else NDArray(x)
                 for x in example_inputs]
    # resolve deferred shapes with one abstract pass
    import jax
    from .. import tune
    # the state scope swallows traced stat writes (BatchNorm running stats)
    # so abstract tracers never land in Parameters; nothing traced here
    # ever runs, so the tuner must not race kernels for it
    with _TraceScope(), autograd.pause(train_mode=training), \
            _rnd._TraceKeyScope(jax.random.PRNGKey(0)), _StateWriteScope(), \
            tune.xla_only("functionalize's shape-only pass"):
        jax.eval_shape(
            lambda *xs: _abstract(net, xs),
            *[jax.ShapeDtypeStruct(x._data.shape, x._data.dtype)
              for x in inputs_nd])

    plist = net.collect_params()
    for p in plist.values():
        if p._data is None:
            p._finish_deferred_init()
    param_list = [plist[k] for k in sorted(plist.keys())]
    params = OrderedDict((p.name, p.data()._data) for p in param_list)

    def apply_fn(params_dict, rng, *input_arrays):
        wrapped = [NDArray(a) for a in input_arrays]
        old = []
        for p in param_list:
            old.append(p._data._data)
            p._data._data = params_dict[p.name]
        try:
            with _TraceScope(), _rnd._TraceKeyScope(rng), \
                    autograd.pause(train_mode=training), \
                    _StateWriteScope() as sw:
                out = net._eager_forward(*wrapped) if hasattr(net, "_eager_forward") \
                    else net(*wrapped)
        finally:
            for p, o in zip(param_list, old):
                p._data._data = o
        flat, rebuild = _flatten_outputs(out)
        return tuple(o._data for o in flat), dict(sw.writes)

    return params, apply_fn


def _abstract(net, xs):
    from ..gluon.block import _flatten_outputs
    wrapped = [NDArray(t) for t in xs]
    out = net._eager_forward(*wrapped) if hasattr(net, "_eager_forward") \
        else net(*wrapped)
    flat, _ = _flatten_outputs(out)
    return tuple(o._data for o in flat)
