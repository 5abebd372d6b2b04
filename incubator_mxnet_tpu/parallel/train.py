"""Fully-compiled SPMD training step over a device mesh.

Reference analog: the steady-state Module.fit loop (SURVEY.md §3.3) where
RunOps iterates pre-built cached engine segments with kvstore push/pull
between forward/backward and update. TPU-native: the WHOLE step — forward,
backward, gradient allreduce, optimizer update, BatchNorm stat update — is
ONE XLA program under jit with NamedShardings; the compiler schedules the
collectives to overlap the backward (what the reference gets from engine
asynchrony + kvstore priority ordering, graph_executor.cc InitOpSegs +
kvstore priority=-key).
"""
from __future__ import annotations

import time
from collections import OrderedDict

import jax
import jax.numpy as jnp

from .. import tune as _tune
from ..base import MXNetError
from ..ops import optimizer_ops as _oo
from .functional import functionalize

__all__ = ["TrainStep", "shard_batch", "default_compiler_options"]


def default_compiler_options():
    """XLA:TPU compile options the framework applies to its jitted hot
    paths. The latency-hiding scheduler overlaps the async HBM prefetch
    copies with compute — measured +8% on the ResNet-50 train step (see
    docs/perf_notes.md). None off-TPU: jaxlib's CPU/GPU flag parsers
    reject TPU-only options."""
    import jax
    if jax.default_backend() != "tpu":
        return None
    return {"xla_tpu_enable_latency_hiding_scheduler": "true"}


def _make_update_rule(opt_name, lr, momentum, wd, opt_kwargs):
    """Map an optimizer name to (state_init, update) built on the REGISTERED
    fused update ops (ops/optimizer_ops.py) — the same kernels the eager
    Trainer path uses, so the compiled and eager optimizers cannot drift.
    Every optimizer_params key must be consumed; leftovers raise, so a typo'd
    or unsupported hyperparameter never silently trains with a default.

    state_init(param) -> tuple of state arrays
    update(w, g, states, t) -> (new_w, new_states); t is the 1-based step.
    """
    import jax.numpy as jnp

    kw = dict(opt_kwargs)
    common = dict(rescale_grad=float(kw.pop("rescale_grad", 1.0)),
                  clip_gradient=float(kw.pop("clip_gradient", -1.0)))

    def _done(rule):
        if kw:
            raise MXNetError(f"TrainStep optimizer {opt_name!r}: unknown "
                             f"optimizer_params {sorted(kw)}")
        return rule

    if opt_name == "sgd" and not momentum:
        return _done((lambda v: (),
                      lambda w, g, st, t: (_oo.sgd_update.fn(
                          w, g, lr=lr, wd=wd, **common), ())))
    if opt_name in ("sgd", "nag"):
        op = _oo.sgd_mom_update if opt_name == "sgd" else _oo.nag_mom_update

        def upd(w, g, st, t, _op=op):
            w2, m2 = _op.fn(w, g, st[0], lr=lr, momentum=momentum, wd=wd,
                            **common)
            return w2, (m2,)
        return _done((lambda v: (jnp.zeros_like(v),), upd))
    if opt_name == "adam":
        b1 = float(kw.pop("beta1", 0.9))
        b2 = float(kw.pop("beta2", 0.999))
        eps = float(kw.pop("epsilon", 1e-8))

        def upd(w, g, st, t):
            # jnp.power, not `float ** t`: a traced t (multi-step scan)
            # sends __rpow__ through a ufunc path that recurses
            tt = jnp.asarray(t, jnp.float32)
            alpha = lr * jnp.sqrt(1 - jnp.power(b2, tt)) / \
                (1 - jnp.power(b1, tt))
            w2, m2, v2 = _oo.adam_update.fn(w, g, st[0], st[1], lr=alpha,
                                            beta1=b1, beta2=b2, epsilon=eps,
                                            wd=wd, **common)
            return w2, (m2, v2)
        return _done((lambda v: (jnp.zeros_like(v), jnp.zeros_like(v)), upd))
    if opt_name == "rmsprop":
        gamma1 = float(kw.pop("gamma1", 0.95))
        eps = float(kw.pop("epsilon", 1e-8))

        def upd(w, g, st, t):
            w2, n2 = _oo.rmsprop_update.fn(w, g, st[0], lr=lr, gamma1=gamma1,
                                           epsilon=eps, wd=wd, **common)
            return w2, (n2,)
        return _done((lambda v: (jnp.zeros_like(v),), upd))
    if opt_name == "signum":
        wd_lh = float(kw.pop("wd_lh", 0.0))

        def upd(w, g, st, t):
            w2, m2 = _oo.signum_update.fn(w, g, st[0], lr=lr,
                                          momentum=momentum, wd=wd,
                                          wd_lh=wd_lh, **common)
            return w2, (m2,)
        return _done((lambda v: (jnp.zeros_like(v),), upd))
    if opt_name == "adamw":
        b1 = float(kw.pop("beta1", 0.9))
        b2 = float(kw.pop("beta2", 0.999))
        eps = float(kw.pop("epsilon", 1e-8))
        eta = float(kw.pop("eta", 1.0))

        def upd(w, g, st, t):
            w2, m2, v2 = _oo.adamw_update.fn(
                w, g, st[0], st[1], lr=lr, beta1=b1, beta2=b2, epsilon=eps,
                eta=eta, wd=wd, clip_gradient=common["clip_gradient"],
                rescale_grad=common["rescale_grad"])
            return w2, (m2, v2)
        return _done((lambda v: (jnp.zeros_like(v), jnp.zeros_like(v)), upd))
    raise MXNetError(f"TrainStep optimizer {opt_name!r} unsupported; one of "
                     "sgd/nag/adam/rmsprop/signum/adamw (or use Trainer)")


def shard_batch(batch, mesh, axis="dp"):
    """Place a host batch onto the mesh sharded on its leading dim (replaces
    gluon.utils.split_and_load's per-GPU copies)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    sharding = NamedSharding(mesh, P(axis))
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), batch)


class TrainStep:
    """Compiled train step for a Gluon net.

    usage:
        step = TrainStep(net, loss_fn, optimizer="sgd",
                         optimizer_params={...}, mesh=mesh,
                         example_inputs=[x, y])
        loss = step(x_batch, y_batch)   # one fused XLA program

    loss_fn(outputs, label_array) -> scalar jax value. Parameters live inside
    TrainStep as a sharded pytree and are written back into the Gluon
    Parameters on `sync()` (for checkpointing / eval through the normal API).
    """

    def __init__(self, net, loss_fn, optimizer="sgd", optimizer_params=None,
                 mesh=None, example_inputs=None, param_spec_fn=None,
                 param_rules=None, data_axis="dp", dtype=None, donate=True):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from .. import profiler as _prof
        from .. import shardlint as _sl

        began = time.time()
        if example_inputs is None:
            raise MXNetError("TrainStep needs example_inputs")
        self.net = net
        self.mesh = mesh
        self.data_axis = data_axis
        opt_kwargs = dict(optimizer_params or {})
        self._lr = float(opt_kwargs.pop("learning_rate", 0.01))
        self._momentum = float(opt_kwargs.pop("momentum", 0.0))
        self._wd = float(opt_kwargs.pop("wd", 0.0))
        self._opt_name = optimizer

        self._dtype = dtype
        params, apply_fn = functionalize(net, example_inputs, training=True)
        if dtype is not None:
            params = OrderedDict((k, v.astype(dtype) if
                                  jnp.issubdtype(v.dtype, jnp.floating) and
                                  "running" not in k else v)
                                 for k, v in params.items())
        self._param_names = list(params.keys())
        self._apply_fn = apply_fn
        self._param_list = [net.collect_params()[k]
                            for k in sorted(net.collect_params().keys())]

        # optimizer state mirrors the param tree; the update rule is built on
        # the registered fused update ops shared with the eager Trainer path
        state_init, update = _make_update_rule(
            optimizer, self._lr, self._momentum, self._wd, opt_kwargs)
        opt_state = {k: state_init(v) for k, v in params.items()}

        # shardings: params replicated (or per param_rules/param_spec_fn),
        # optimizer state sharded exactly like its weight, batch on dp
        if mesh is not None:
            if param_rules is not None and param_spec_fn is not None:
                raise MXNetError("TrainStep takes param_rules OR "
                                 "param_spec_fn, not both")
            if param_rules is not None:
                # regex table; an unmatched non-scalar leaf is an ERROR —
                # silent fall-to-replication is the SL04 bug class
                from .partition import match_partition_rules
                pspec = match_partition_rules(
                    param_rules, params, on_unmatched="error",
                    key=f"trainstep:{optimizer}")
            else:
                pspec = {}
                for k, v in params.items():
                    s = param_spec_fn(k, v) if param_spec_fn else P()
                    if s is None:
                        # a None spec used to flow into NamedSharding and
                        # die with an opaque TypeError — name the leaf and
                        # demand an explicit decision instead
                        raise MXNetError(
                            f"param_spec_fn returned None for {k!r}; "
                            f"return PartitionSpec() to replicate this "
                            f"leaf explicitly (or use param_rules=)")
                    pspec[k] = s
                if _sl.enabled():
                    # explicit fn (or the documented replicate-all
                    # default) counts as declared — SL04 stays quiet
                    _sl.record_partition(
                        f"trainstep:{optimizer}", leaves=list(params),
                        matched={k: "param_spec_fn" for k in params}
                        if param_spec_fn else {},
                        unmatched=[],
                        replicated=[] if param_spec_fn else list(params))
            param_sh = {k: NamedSharding(mesh, s) for k, s in pspec.items()}
            params = {k: jax.device_put(v, param_sh[k])
                      for k, v in params.items()}
            opt_state = {k: tuple(jax.device_put(s, param_sh[k]) for s in st)
                         for k, st in opt_state.items()}
            self._data_sharding = NamedSharding(mesh, P(data_axis))
        else:
            self._data_sharding = None

        self.params = dict(params)
        self.opt_state = opt_state
        self._step_count = 0
        # inputs that arrived already carrying the step's data sharding
        # (io.prefetch pre-placed them) and skipped the _to_device copy
        self.preplaced_hits = 0
        non_diff = {p.name for p in self._param_list if p.grad_req == "null"}

        # One behaviour on one device as on a mesh: every tuned site in the
        # forward pass takes XLA (tune.xla_only). A Pallas candidate's
        # backward is the vjp of the XLA reference on top of its own
        # forward, which a race of forward calls cannot see.
        withheld_why = ("TrainStep differentiates its forward pass: the "
                        "race times a forward call on the host clock, and "
                        "a Mosaic kernel cannot be partitioned over a mesh")

        def step_fn(params, opt_state, rng, step_i, *batch):
            inputs, label = batch[:-1], batch[-1]

            def loss_of(diff_params):
                full = dict(params)
                full.update(diff_params)
                with (_tune.xla_only(withheld_why),
                      jax.named_scope("forward")):
                    outs, writes = apply_fn(full, rng, *inputs)
                out = outs[0]
                with jax.named_scope("loss"):
                    return loss_fn(out, label), (writes, out)

            diff_params = {k: v for k, v in params.items() if k not in non_diff}
            (loss, (writes, out)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(diff_params)

            new_params = dict(params)
            new_opt = dict(opt_state)
            with jax.named_scope("optimizer"):
                t = step_i + 1
                for k, g in grads.items():
                    w = params[k]
                    new_params[k], new_opt[k] = update(
                        w, g.astype(w.dtype), opt_state[k], t)
                # fold state writes (BN running stats) into the param tree
                for k, v in writes.items():
                    new_params[k] = v.astype(params[k].dtype)
            return new_params, new_opt, loss

        self._step_fn = step_fn
        # donation is requested only where the backend actually aliases
        # buffers (same gate as the fused optimizer path): on CPU a
        # donated-then-ignored buffer would still be poisoned for the
        # caller on any backend that honors deletion
        self._donate = bool(donate) and _oo._donation_supported()
        self._copts = default_compiler_options()
        self._jit_key = f"trainstep:{optimizer}"
        # declare what the step's args mean so the shardlint donation
        # audit (SL03) and bf16 rule (SL02) can judge this program
        _sl.annotate(self._jit_key,
                     arg_roles={0: "params", 1: "opt_state", 2: "rng",
                                3: "step"},
                     declared_bf16=(dtype is not None and
                                    jnp.dtype(dtype) == jnp.bfloat16))
        # the whole step routes through the two-tier executable cache —
        # it was the one hot jit in the package that escaped both
        # track_jit telemetry and the AOT/disk tier
        from .. import compile_cache as _cc
        self._jit_step = _cc.cached_jit(
            self._jit_key, step_fn,
            donate_argnums=(0, 1) if self._donate else (),
            compiler_options=self._copts)
        self._jit_multi = {}
        _prof.setup_row("train_step_init", self._jit_key, began, time.time())

    def _to_device(self, batch):
        import jax
        from ..ndarray.ndarray import NDArray
        arrs = []
        for i, b in enumerate(batch):
            a = b._data if isinstance(b, NDArray) else jax.numpy.asarray(b)
            # with a compute dtype set, float NETWORK inputs follow it
            # (params were cast in __init__; mixed conv dtypes are an XLA
            # error). The label (last position, consumed only by loss_fn) is
            # never cast: float-encoded class indices above 256 are not
            # representable in bfloat16, so casting would silently corrupt
            # the training targets.
            if self._dtype is not None and i < len(batch) - 1 and \
                    jnp.issubdtype(a.dtype, jnp.floating):
                a = a.astype(self._dtype)
            if self._data_sharding is not None:
                # batches staged through io.prefetch arrive ALREADY carrying
                # this NamedSharding — re-issuing device_put would serialize
                # a no-op transfer into the step; skip it
                if getattr(a, "sharding", None) == self._data_sharding:
                    self.preplaced_hits += 1
                else:
                    a = jax.device_put(a, self._data_sharding)
            arrs.append(a)
        return arrs

    def run_epoch(self, data_iter, prefetch=2, checkpoint=None,
                  checkpoint_every=0, start_batch=0):
        """Drive one pass over ``data_iter`` with the device input pipeline:
        the iterator is wrapped in io.prefetch (sharded over the mesh's
        data axis when the step has one) so batch N+1's host->HBM copy
        overlaps batch N's compiled step, and pre-placed shards skip the
        step's own device_put. An already-constructed DevicePrefetcher is
        consumed as-is (its placement target wins). Batches may be
        (x..., label) tuples/lists or a single array. Returns the per-step
        losses as an NDArray.

        Fault tolerance: with ``checkpoint`` (a fault.CheckpointManager /
        AsyncCheckpointManager) and ``checkpoint_every=N``, every N-th
        batch snapshots params + optimizer state + the batch cursor
        (write-behind when the manager is async, so the step never waits
        on disk). ``start_batch`` fast-forwards the source iterator — pass
        the ``data_state['batch']`` of the restored checkpoint to resume
        mid-epoch with no skipped or repeated batches."""
        from ..io.prefetch import DevicePrefetcher, prefetch_to_device
        from ..ndarray.ndarray import NDArray
        it, owned = data_iter, False
        if not isinstance(it, DevicePrefetcher):
            it = prefetch_to_device(iter(it), size=prefetch, mesh=self.mesh,
                                    axis=self.data_axis,
                                    skip_batches=start_batch)
            owned = True
        elif start_batch:
            raise MXNetError("start_batch needs an unwrapped source "
                             "iterator (pass skip_batches to io.prefetch "
                             "when constructing the DevicePrefetcher)")
        from .. import fault as _fault
        from .. import profiler as _prof
        losses = []
        flight = _fault.flight_enabled()
        src = iter(it)
        _end = object()
        try:
            while True:
                # manual next() so the host-side wait on the input
                # pipeline is attributable (with MXNET_STEP_ATTRIBUTION
                # off the span books nothing: a trace annotation while a
                # profiler session records, else a shared no-op)
                with _prof.span("input_wait"):
                    batch = next(src, _end)
                if batch is _end:
                    break
                if not isinstance(batch, (tuple, list)):
                    batch = (batch,)
                losses.append(self(*batch))
                if checkpoint is not None and checkpoint_every and \
                        it.cursor % checkpoint_every == 0:
                    with _prof.span("ckpt_snapshot"):
                        self.save_checkpoint(
                            checkpoint, data_state={"batch": it.cursor})
                _prof.phase_step_end()
                if flight:
                    _fault.flight_record(
                        "step", step=self._step_count, cursor=it.cursor,
                        phases=_prof.last_step_phases() or None)
        except Exception as e:
            # the postmortem hook the kill/fault tests rely on: dump the
            # flight ring before the exception unwinds the train loop
            # (no-op when MXNET_FLIGHT_RECORDER is unset)
            _fault.flight_dump(f"exception:{type(e).__name__}")
            raise
        finally:
            if owned:
                it.close()
        if not losses:
            return NDArray(jnp.zeros((0,), jnp.float32))
        return NDArray(jnp.stack([getattr(l, "_data", l) for l in losses]))

    def save_checkpoint(self, manager, data_state=None, extra=None):
        """Snapshot the compiled step's params + optimizer state (+ an
        opaque ``data_state`` cursor) through a fault.CheckpointManager.
        An AsyncCheckpointManager makes this write-behind: the only
        step-blocking cost is the device->host copy."""
        flat = {}
        for k, v in self.params.items():
            flat[f"p/{k}"] = jax.device_get(v)
        for k, st in self.opt_state.items():
            for i, s in enumerate(st):
                flat[f"o{i}/{k}"] = jax.device_get(s)
        save = getattr(manager, "save_async", manager.save)
        save(self._step_count, params=flat, extra=extra,
             data_state=data_state)

    def load_checkpoint(self, manager, step=None):
        """Restore params/opt-state saved by :meth:`save_checkpoint` onto
        this step's current shardings; rewinds ``_step_count``. Returns
        ``(step, data_state)`` — feed ``data_state['batch']`` back into
        ``run_epoch(start_batch=...)`` for a mid-epoch-exact resume."""
        step, arrays, data_state = manager.restore_arrays(step)
        host = {k: getattr(v, "_data", v) for k, v in arrays.items()}

        def _placed(tag, like):
            a = jnp.asarray(host[tag]).astype(like.dtype)
            sh = getattr(like, "sharding", None)
            return jax.device_put(a, sh) if sh is not None else a

        missing = [k for k in self.params if f"p/{k}" not in host]
        if missing:
            raise MXNetError(f"checkpoint step {step} lacks params "
                             f"{missing[:3]}... — saved by a different "
                             "model?")
        self.params = {k: _placed(f"p/{k}", v)
                       for k, v in self.params.items()}
        self.opt_state = {
            k: tuple(_placed(f"o{i}/{k}", s) for i, s in enumerate(st))
            for k, st in self.opt_state.items()}
        self._step_count = step
        return step, data_state

    def trace_for_analysis(self, *batch):
        """Trace (but do not compile or run) the step for this batch
        signature. With MXNET_SHARDLINT capture on, this feeds the full
        step jaxpr to the analyzer — the tools/shardlint offline corpus
        drives TrainStep entries through here so `python -m
        tools.shardlint` never pays an XLA compile for them."""
        from ..ndarray import random as _rnd
        arrs = self._to_device(batch)
        rng = _rnd.next_key()
        tracer = getattr(self._jit_step, "trace_signature", None)
        if tracer is not None:
            tracer(self.params, self.opt_state, rng, self._step_count,
                   *arrs)

    def __call__(self, *batch):
        from ..ndarray import random as _rnd
        from .. import fault as _fault
        from .. import profiler as _prof
        # train_step and rng are trace-only: under the gate the step's
        # books are h2d + compute as before (profiler.py, above `span`)
        with _prof.span("train_step", book=False):
            _fault.inject("step")       # MXNET_FAULT_INJECT test hook
            attr = _prof.attribution_enabled()
            with _prof.span("h2d"):
                arrs = self._to_device(batch)
            with _prof.span("rng", book=False):
                rng = _rnd.next_key()
            with _prof.span("compute"):
                self.params, self.opt_state, loss = self._jit_step(
                    self.params, self.opt_state, rng, self._step_count,
                    *arrs)
                if attr:
                    # dispatch is async: the compute span is only real
                    # wall time if we sync on the result. Gated on
                    # attribution so the un-attributed hot path keeps
                    # XLA's pipelining.
                    _block = getattr(loss, "block_until_ready", None)
                    if _block is not None:
                        _block()
            self._step_count += 1
        return loss

    def run_steps(self, n, *batch):
        """Run `n` optimizer steps on ONE batch inside a single XLA program
        (lax.scan over the step, params/opt-state carried on device).

        The whole loop is one dispatch: no host round-trip per step (the
        reference gets the same effect from engine op-bulking,
        graph_executor.cc:1288 InitOpSegs). Per-step RNG
        is fold_in(step_index). Returns the per-step losses as an NDArray.
        """
        import jax
        from jax import lax
        from ..ndarray.ndarray import NDArray
        from ..ndarray import random as _rnd

        arrs = self._to_device(batch)

        fn = self._jit_multi.get(n)
        if fn is None:
            step_fn = self._step_fn

            def multi(params, opt_state, rng, step0, *batch_):
                def body(carry, i):
                    p, o = carry
                    r = jax.random.fold_in(rng, i)
                    p, o, loss = step_fn(p, o, r, step0 + i, *batch_)
                    return (p, o), loss
                (p, o), losses = lax.scan(body, (params, opt_state),
                                          jnp.arange(n))
                return p, o, losses

            fn = jax.jit(multi,
                         donate_argnums=(0, 1) if self._donate else (),
                         compiler_options=self._copts)
            # bounded FIFO, like OpDef._jit_cache: each entry retains a
            # whole compiled n-step executable
            if len(self._jit_multi) >= 8:
                self._jit_multi.pop(next(iter(self._jit_multi)))
            self._jit_multi[n] = fn

        rng = _rnd.next_key()
        self.params, self.opt_state, losses = fn(
            self.params, self.opt_state, rng, self._step_count, *arrs)
        self._step_count += n
        return NDArray(losses)

    def sync(self):
        """Write the compiled-step params back into the Gluon Parameters so
        save_parameters()/eval see the trained weights. Mesh-sharded arrays
        are gathered to the default device — the eager path runs single-chip."""
        import numpy as _np
        import jax.numpy as _jnp
        for p in self._param_list:
            if p.name in self.params:
                v = self.params[p.name]
                if getattr(v, "sharding", None) is not None and \
                        len(getattr(v.sharding, "device_set", ())) > 1:
                    v = _jnp.asarray(_np.asarray(v))
                p._data._data = v.astype(p.data().dtype)
