"""SPMD Transformer language model (GPT-style, pre-norm).

Purpose: the multi-parallel flagship — data (dp), tensor (tp, Megatron
column/row pairing), and sequence/context (sp, ring attention) parallelism in
ONE jitted train step over a jax.sharding.Mesh. The reference's closest
artifacts are the fused attention matmul ops (src/operator/contrib/
transformer.cc) and the PTB word_lm example; it has no TP/SP at all
(SURVEY.md §2.3), so this model is where the TPU build goes beyond parity.

Functional style: params = flat dict name -> jax.Array; every name maps to a
PartitionSpec via parallel.tensor_parallel.transformer_param_specs.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax.ad_checkpoint import checkpoint_name

from ..parallel.ring_attention import attention_reference, ring_attention


def _remat_policy(name):
    """Map TransformerConfig.remat_policy to a jax.checkpoint policy
    (None = recompute everything; reference analog: the
    MXNET_BACKWARD_DO_MIRROR recompute knob, graph_executor.cc:351)."""
    if not name:
        return None
    cp = jax.checkpoint_policies
    table = {
        "dots": cp.checkpoint_dots,
        "dots_no_batch": cp.checkpoint_dots_with_no_batch_dims,
        "save_attn": cp.save_only_these_names("attn_out"),
        "save_attn_mlp": cp.save_only_these_names("attn_out", "mlp_out"),
        "save_mlp": cp.save_only_these_names("mlp_out"),
    }
    if name not in table:
        raise ValueError(f"unknown remat_policy {name!r}; "
                         f"one of {sorted(table)}")
    return table[name]

__all__ = ["TransformerConfig", "TransformerLM"]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 6
    d_ff: int = 2048
    max_len: int = 2048
    dtype: str = "bfloat16"
    remat: bool = True          # jax.checkpoint each block (HBM for FLOPs)
    # Selective rematerialization policy. None = recompute everything
    # (the recomputed forward's share of a step is `remat_time_share`,
    # PERF.md section 5). "dots" / "dots_no_batch" are XLA's stock
    # save-matmul-outputs policies; "save_attn" / "save_attn_mlp" save
    # the named per-block outputs (attn_out, mlp_out) and recompute the
    # rest.
    remat_policy: str | None = None
    # Pallas blocked flash attention for the non-sp path (O(T) memory,
    # parallel/flash_attention.py); the sp path always uses ring
    # attention. On by default: it beats the dense path end to end at
    # T = 2,048 and is the only path that compiles at long context
    # (docs/perf_notes.md; speeds in PERF.md section 5). Untileable
    # shapes fall back to attention_reference inside flash_attention().
    flash_attention: bool = True


class TransformerLM:
    def __init__(self, config: TransformerConfig):
        self.cfg = config

    # -- parameters ---------------------------------------------------------
    def init_params(self, key):
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        d, h, f = cfg.d_model, cfg.n_heads, cfg.d_ff
        params = {}
        k = iter(jax.random.split(key, 4 + 8 * cfg.n_layers))

        def dense(key, fan_in, shape):
            return (jax.random.normal(key, shape, jnp.float32) /
                    math.sqrt(fan_in)).astype(dt)

        params["embed"] = dense(next(k), d, (cfg.vocab_size, d))
        params["pos_embed"] = dense(next(k), d, (cfg.max_len, d))
        for i in range(cfg.n_layers):
            p = f"layer{i}_"
            params[p + "ln1_g"] = jnp.ones((d,), dt)
            params[p + "ln1_b"] = jnp.zeros((d,), dt)
            params[p + "wq"] = dense(next(k), d, (d, d))
            params[p + "wk"] = dense(next(k), d, (d, d))
            params[p + "wv"] = dense(next(k), d, (d, d))
            params[p + "wo"] = dense(next(k), d, (d, d))
            params[p + "ln2_g"] = jnp.ones((d,), dt)
            params[p + "ln2_b"] = jnp.zeros((d,), dt)
            params[p + "w_in"] = dense(next(k), d, (d, f))
            params[p + "w_out"] = dense(next(k), f, (f, d))
        params["lnf_g"] = jnp.ones((d,), dt)
        params["lnf_b"] = jnp.zeros((d,), dt)
        return params

    # -- forward ------------------------------------------------------------
    def _ln(self, x, g, b):
        m = jnp.mean(x.astype(jnp.float32), axis=-1, keepdims=True)
        v = jnp.var(x.astype(jnp.float32), axis=-1, keepdims=True)
        return ((x - m) * jax.lax.rsqrt(v + 1e-5)).astype(x.dtype) * g + b

    def _block(self, params, prefix, x, sp_axis, tp_axis=None, mesh=None):
        """One pre-norm block. Inside shard_map, attention/MLP weights may be
        Megatron-sharded over `tp_axis` (wq/wk/wv/w_in column-parallel,
        wo/w_out row-parallel): each device computes its local slice of heads
        / hidden units and a psum over tp after each row-parallel matmul
        restores the full residual stream. Head/hidden split is read off the
        *local* weight shapes, so the same code serves the unsharded path.
        `mesh` is the multi-device mesh of a pure-jit (GSPMD) caller: the
        flash kernel then runs per shard."""
        with jax.named_scope("attn"):
            x = x + self._attn(params, prefix, x, sp_axis, tp_axis, mesh)
        with jax.named_scope("mlp"):
            h = self._ln(x, params[prefix + "ln2_g"],
                         params[prefix + "ln2_b"])
            y = jax.nn.gelu(h @ params[prefix + "w_in"]) \
                @ params[prefix + "w_out"]
            if tp_axis is not None:
                y = jax.lax.psum(y, tp_axis)
            y = checkpoint_name(y, "mlp_out")
            return x + y

    def _attn(self, params, prefix, x, sp_axis, tp_axis, mesh):
        """The attention half of a block: ln1, projections, attention, the
        output projection (psum over tp where sharded). Returns attn_out."""
        cfg = self.cfg
        B, T, D = x.shape
        hd = D // cfg.n_heads
        h = self._ln(x, params[prefix + "ln1_g"], params[prefix + "ln1_b"])
        wq = params[prefix + "wq"]
        d_local = wq.shape[1]          # = D/tp inside shard_map with TP
        h_local = d_local // hd        # local head count
        q = (h @ wq).reshape(B, T, h_local, hd)
        kk = (h @ params[prefix + "wk"]).reshape(B, T, h_local, hd)
        v = (h @ params[prefix + "wv"]).reshape(B, T, h_local, hd)
        if sp_axis is not None:
            attn = ring_attention(q, kk, v, sp_axis, causal=True)
        elif self.cfg.flash_attention:
            # (B,T,H,hd) is what the kernels index: two 64-wide heads
            # to a block of 128 lanes of the projections' own (B,T,D), so
            # no copy stands round a call (flash_attention._direct; an
            # odd local head count still goes through a transpose).
            # Emitting (BH,T,hd) from the projection einsums instead,
            # tried while the kernels wanted that layout, was 4.4% slower
            # end to end than transposing: XLA's bhtk-output einsum cost
            # more than the copies it saved. flash_attention_bh stays for
            # callers that already hold (BH,T,D).
            from ..parallel.flash_attention import flash_attention
            attn_fn = functools.partial(flash_attention, causal=True)
            if mesh is not None:
                # a Mosaic kernel cannot be partitioned automatically (jax
                # refuses at lowering): run it per shard. Attention is
                # independent per sequence and per head, which is exactly
                # how GSPMD lays q/k/v out — batch over dp, heads over tp.
                from ..parallel._compat import shard_map
                spec = P("dp" if "dp" in mesh.axis_names else None, None,
                         "tp" if "tp" in mesh.axis_names else None, None)
                attn_fn = shard_map(attn_fn, mesh, (spec,) * 3, spec)
            attn = attn_fn(q, kk, v)
        else:
            attn = attention_reference(q, kk, v, causal=True)
        attn_out = attn.reshape(B, T, d_local) @ params[prefix + "wo"]
        if tp_axis is not None:
            attn_out = jax.lax.psum(attn_out, tp_axis)
        return checkpoint_name(attn_out, "attn_out")

    def apply(self, params, tokens, sp_axis=None, positions=None, tp_axis=None,
              mesh=None):
        """tokens (B, T) int32 -> logits (B, T, vocab). When called inside a
        shard_map with a sequence axis, pass sp_axis and per-shard positions;
        pass tp_axis when attention/MLP weights are Megatron-sharded; pass
        the mesh when tracing a pure-jit program over several devices."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = params["embed"][tokens]
            if positions is None:
                positions = jnp.arange(tokens.shape[1])
            x = x + params["pos_embed"][positions]
        if cfg.remat:
            block = jax.checkpoint(
                lambda p, pref, y: self._block(p, pref, y, sp_axis, tp_axis,
                                               mesh),
                static_argnums=(1,), policy=_remat_policy(cfg.remat_policy))
        else:
            block = lambda p, pref, y: self._block(p, pref, y, sp_axis,
                                                   tp_axis, mesh)
        for i in range(cfg.n_layers):
            with jax.named_scope(f"layer{i}"):
                x = block(params, f"layer{i}_", x)
        with jax.named_scope("final_ln"):
            x = self._ln(x, params["lnf_g"], params["lnf_b"])
        with jax.named_scope("logits"):
            return (x @ params["embed"].T).astype(jnp.float32)

    def loss(self, params, tokens, targets, sp_axis=None, positions=None,
             tp_axis=None, mesh=None):
        # `forward`, `loss` and (in the train step) `optimizer` are the top
        # words a device trace is read by (PERF.md section 3)
        with jax.named_scope("forward"):
            logits = self.apply(params, tokens, sp_axis, positions, tp_axis,
                                mesh)
        with jax.named_scope("loss"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, targets[..., None],
                                       axis=-1)[..., 0]
            return jnp.mean(nll)

    # -- sharded training ---------------------------------------------------
    def param_sharding(self, mesh, tp_axis="tp"):
        from ..parallel.tensor_parallel import transformer_param_specs
        has_tp = tp_axis in mesh.axis_names
        shd = {}
        for name in self._param_names():
            shd[name] = NamedSharding(
                mesh, transformer_param_specs(name, _FakeNd(2), tp_axis)
                if has_tp and _rank_of(name) >= 2 else P())
        return shd

    def _param_names(self):
        names = ["embed", "pos_embed", "lnf_g", "lnf_b"]
        for i in range(self.cfg.n_layers):
            p = f"layer{i}_"
            names += [p + s for s in ("ln1_g", "ln1_b", "wq", "wk", "wv", "wo",
                                      "ln2_g", "ln2_b", "w_in", "w_out")]
        return names

    def make_train_step(self, mesh, lr=1e-3, use_sp=True, n_steps=None):
        """Fully-sharded train step: dp on batch, tp on weights, sp on
        sequence (ring attention through shard_map). Adam in fp32 master
        precision. Returns (step_fn, shard_params_fn, init_opt_fn);
        step_fn(params, opt_state, tokens, targets, step_i) -> (params,
        opt_state, loss) with params/opt_state donated.

        n_steps: compile a MULTI-step program — lax.scan of the step with
        params/opt carried on device, one dispatch for the whole window
        (the TrainStep.run_steps analog; per-step RNG/step_i advance in
        the scan)."""
        from ..parallel._compat import shard_map
        from ..parallel.tensor_parallel import transformer_param_specs

        axis_names = mesh.axis_names
        has = {a: a in axis_names for a in ("dp", "tp", "sp")}
        sp_axis = "sp" if (use_sp and has["sp"]) else None

        def _is_matmul(n):
            return n.endswith(("wq", "wk", "wv", "wo", "w_in", "w_out"))

        # weights are tp-sharded only when the mesh actually has a 'tp' axis.
        # On the shard_map (sp) path the block does manual Megatron TP, so
        # only the attention/MLP matmul weights are sharded and the embedding
        # stays replicated (apply() indexes the full table in-shard); on the
        # pure-jit GSPMD path XLA handles any spec, embedding included.
        if sp_axis is not None:
            pspec = {n: (transformer_param_specs(n, _FakeNd(2))
                         if has["tp"] and _is_matmul(n) else P())
                     for n in self._param_names()}
        else:
            pspec = {n: (transformer_param_specs(n, _FakeNd(2))
                         if has["tp"] and _rank_of(n) >= 2 else P())
                     for n in self._param_names()}
        data_spec = P("dp" if has["dp"] else None,
                      sp_axis)

        model = self
        tp_in_block = "tp" if (sp_axis is not None and has["tp"]) else None

        def loss_fn(params, tokens, targets):
            if sp_axis is not None:
                # sequence-sharded path: positions differ per shard
                def local(params_, tokens_, targets_):
                    idx = jax.lax.axis_index(sp_axis)
                    t_local = tokens_.shape[1]
                    positions = idx * t_local + jnp.arange(t_local)
                    l = model.loss(params_, tokens_, targets_, sp_axis,
                                   positions, tp_in_block)
                    terms = jax.lax.pmean(l, sp_axis)
                    if has["dp"]:
                        terms = jax.lax.pmean(terms, "dp")
                    if has["tp"]:
                        terms = jax.lax.pmean(terms, "tp")
                    return terms

                fn = shard_map(local, mesh,
                               (pspec, data_spec, data_spec), P())
                return fn(params, tokens, targets)
            return model.loss(params, tokens, targets,
                              mesh=mesh if mesh.devices.size > 1 else None)

        from ..parallel.train import _make_update_rule
        _, adam_rule = _make_update_rule("adam", lr, 0.0, 0.0, {})

        def step(params, opt_state, tokens, targets, step_i):
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets)
            new_params, new_opt = {}, {}
            with jax.named_scope("optimizer"):
                t = step_i + 1
                for k, g in grads.items():
                    # fp32 master weights around the shared adam rule
                    w32, new_opt[k] = adam_rule(
                        params[k].astype(jnp.float32), g.astype(jnp.float32),
                        opt_state[k], t)
                    new_params[k] = w32.astype(params[k].dtype)
            return new_params, new_opt, loss

        if n_steps:
            from jax import lax

            def multi(params, opt_state, tokens, targets, step0,
                      _one=step):
                def body(carry, i):
                    p, o = carry
                    p, o, l = _one(p, o, tokens, targets, step0 + i)
                    return (p, o), l
                (p, o), losses = lax.scan(body, (params, opt_state),
                                          jnp.arange(n_steps))
                return p, o, losses[-1]

            step = multi

        param_sh = {n: NamedSharding(mesh, s) for n, s in pspec.items()}
        opt_sh = {n: (param_sh[n], param_sh[n]) for n in pspec}
        data_sh = NamedSharding(mesh, data_spec)
        # outputs pinned to the input layout: left to the compiler, a
        # replicated leaf can come back sharded and the next (donating)
        # call then refuses it
        jit_step = jax.jit(step,
                           in_shardings=(param_sh, opt_sh, data_sh, data_sh,
                                         None),
                           out_shardings=(param_sh, opt_sh, None),
                           donate_argnums=(0, 1))

        def shard_params(params):
            # jnp.asarray copy first: device_put may alias the source buffer
            # (zero-copy on CPU), and the donated step would then delete the
            # caller's arrays with it
            return {k: jax.device_put(jnp.asarray(v).copy(),
                                      NamedSharding(mesh, pspec[k]))
                    for k, v in params.items()}

        def init_opt(params):
            # born with the step's layout: unplaced zeros would all land
            # on device 0 first and give the first call a signature (and a
            # trace) of its own
            return {k: (jnp.zeros(v.shape, jnp.float32, device=param_sh[k]),
                        jnp.zeros(v.shape, jnp.float32, device=param_sh[k]))
                    for k, v in params.items()}

        return jit_step, shard_params, init_opt


def _rank_of(name):
    if name in ("embed", "pos_embed") or name.endswith(("wq", "wk", "wv", "wo",
                                                        "w_in", "w_out")):
        return 2
    return 1


class _FakeNd:
    def __init__(self, ndim):
        self.ndim = ndim
        self.shape = (1,) * ndim
