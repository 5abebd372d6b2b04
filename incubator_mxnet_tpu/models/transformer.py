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
import itertools
import math
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax.ad_checkpoint import checkpoint_name

from ..parallel.flash_attention import RESIDUAL_NAMES
from ..parallel.ring_attention import attention_reference, ring_attention
from ..parallel.sparse_attention import BlockSelect, block_sparse_attention


def _remat_policy(name):
    """Map TransformerConfig.remat_policy to a jax.checkpoint policy
    (reference analog: the MXNET_BACKWARD_DO_MIRROR recompute knob,
    graph_executor.cc:351). Every policy, None among them, keeps what a
    flash forward kernel leaves for its backward (its output and lse, 66 MB
    a layer at GPT-2 medium's sizes): with both kept the kernel is not run
    again; None recomputes everything else."""
    cp = jax.checkpoint_policies
    flash = cp.save_only_these_names(*RESIDUAL_NAMES)
    if not name:
        return flash
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
    return cp.save_from_both_policies(table[name], flash)

__all__ = ["TransformerConfig", "TransformerLM", "Rotary", "GQA", "Experts"]


@dataclasses.dataclass(frozen=True)
class Rotary:
    """Rotary embedding of a "gqa" layer: over the first `share` of each
    head (the halves of that part paired, as _rope pairs a whole head's),
    base `theta`. `yarn` = (factor, original_len, beta_fast, beta_slow,
    attention_factor): YaRN's frequencies (yarn_inv_freq), cos and sin
    times the attention factor."""
    theta: float = 10000.0
    share: float = 1.0
    yarn: tuple | None = None


@dataclasses.dataclass(frozen=True)
class GQA:
    """One "gqa" layer: `heads` query heads over the model's n_kv_heads K/V
    heads of head_dim; causal, and with a `window` query i reads keys j with
    i - j < window; with `qk_norm` an RMSNorm of head_dim (a learned weight)
    on each head of q and of k before rotary; with `gate` a sigmoid output
    gate A HEAD (a (d_model, heads) projection of the block's input) before
    the output projection."""
    heads: int
    window: int | None = None
    rotary: Rotary = Rotary()
    qk_norm: bool = False
    gate: bool = True


@dataclasses.dataclass(frozen=True)
class Experts:
    """An "experts" layer's MLP: a router over `count` experts, a token's
    `per_token` largest scores (`score`: "sigmoid" | "softmax", float32),
    weights scaling * s / sum(s) (`norm_topk`) or scaling * s; of the
    chosen, this chip computes the experts [held[0], held[0] + held[1]),
    each a SwiGLU of `width` (parallel/moe.moe_routed: `rows` the one
    buffer's rows); beside them one ungated shared SwiGLU of `shared_width`
    (0: none). With `bias_rate` the layer
    carries a bias over all `count` experts: a token's experts are the
    `per_token` largest of s + bias, weighted by s alone, and after each
    step of `make_train_step` the bias moves by bias_rate towards an even
    load (parallel/moe.moe_balance)."""
    count: int
    held: tuple
    per_token: int
    width: int
    shared_width: int = 0
    score: str = "sigmoid"
    scaling: float = 1.0
    norm_topk: bool = True
    rows: int = 0
    bias_rate: float | None = None


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """Sizes and switches of a TransformerLM. The defaults are GPT-2's block:
    LayerNorm, learned positions, multi-head attention, a GELU MLP, the
    output projection tied to the embedding. `mixers` names each layer's
    mixer instead, one word a layer (empty: "mha" in every layer):

    | mixer | what it computes | what it reads |
    |---|---|---|
    | "mha" | causal softmax attention, n_heads heads of d_model / n_heads; ring attention over `sp`, Megatron heads over `tp` | n_heads, flash_attention |
    | "sparse" | grouped-query causal softmax attention, n_heads query heads over n_kv_heads K/V heads of d_model / n_heads, no rotary, sigmoid output gate; up to select.dense_len positions dense (the flash kernels), beyond that over the blocks InfLLM-v2 selection picks (parallel/sparse_attention.py) | n_heads, n_kv_heads, select, flash_attention |
    | "lightning" | decayed linear attention in chunks (parallel/linear_attention.py): n_heads heads of d_model / n_heads, RMSNorm on each head of q and k, rotary over the whole head, decay exp(-2^(-8 (h + 1) / n_heads)), RMSNorm over all heads of the result, sigmoid output gate | n_heads, rope_theta |
    | "gqa" | grouped-query causal softmax attention, the layer's own count of query heads over n_kv_heads K/V heads of head_dim, QK-norm where the layer asks, rotary over a share of the head (YaRN's frequencies where given), full or windowed (the flash kernels; the windowed ones run the band alone), a sigmoid output gate a head unless the layer drops it | gqa[layer] (GQA), n_kv_heads, head_dim, flash_attention |
    | "conv" | LFM2's gated short convolution: B, C, x~ = split(h W_bcx), y = (C * causalconv(B * x~)) W_o, the convolution depthwise over the 3 taps of `conv_w` along T (short_conv), no bias | d_model |

    `mlps` names each layer's MLP, one word a layer (empty: "dense" in
    every layer): "dense" is `mlp` at d_ff, "experts" the routed and shared
    experts of `experts` (Experts). Every layer shares `norm`,
    `residual_scale`; a model with no "mha" layer needs no
    `learned_positions`."""
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 6
    d_ff: int = 2048
    max_len: int = 2048
    dtype: str = "bfloat16"
    remat: bool = True          # jax.checkpoint each block (HBM for FLOPs)
    # Selective rematerialization policy. None = recompute everything but
    # the flash kernel's forward, whose output and lse are kept
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
    # -- what a layer list needs; the defaults above and below are GPT-2's --
    mixers: tuple = ()              # a word a layer: the table above
    norm: str = "layernorm"         # or "rmsnorm": learned weight, no bias
    norm_eps: float = 1e-5
    mlp: str = "gelu"               # or "swiglu": (silu(h Wg) * (h Wu)) Wd
    learned_positions: bool = True  # a (max_len, d_model) table at the input
    tied_head: bool = True          # False: `head`, a (vocab, d_model) matrix
    n_kv_heads: int | None = None   # "sparse": K/V heads (None: n_heads)
    rope_theta: float = 10000.0     # "lightning": rotary base
    select: BlockSelect = BlockSelect()     # "sparse": InfLLM-v2's sizes
    embed_scale: float = 1.0        # x = embed_scale * E[token]
    residual_scale: float = 1.0     # x += residual_scale * f(norm(x))
    logit_scale: float = 1.0        # logits = (logit_scale * norm(x)) W
    head_dim: int | None = None     # None: d_model // n_heads
    gqa: tuple = ()                 # "gqa": a GQA a layer (None elsewhere)
    mlps: tuple = ()                # a word a layer: "dense" | "experts"
    experts: Experts | None = None  # what an "experts" layer holds


MIXERS = ("mha", "sparse", "lightning", "gqa", "conv")
MLPS = ("dense", "experts")


def _layer_of(prefix):
    """The index i of a block's parameter prefix "layer{i}_"."""
    return int(prefix[len("layer"):-1])


def _rms(x, g, eps):
    """RMSNorm over the last axis in float32, learned weight, no bias."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                            + eps)
    return y.astype(x.dtype) * g


def _scaled(x, scale):
    """x times a width scale, multiplied in float32; x itself at 1."""
    if scale == 1.0:
        return x
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _is_state(name):
    """Model state that a step moves by a rule of its own, not by its
    gradient (an expert layer's selection bias): float32, no gradient, no
    optimizer state."""
    return name.endswith("_e_bias")


def short_conv(u, w):
    """LFM2's causal depthwise convolution along T: v_t = sum over j of
    w_j * u_(t - L + 1 + j), zeros before t = 0. u (B, T, C), w (L, C):
    the last tap reads position t itself. L shifted multiply-adds,
    accumulated in float32 and rounded to u's dtype once (bfloat16 sums
    would round at each tap); elementwise work that XLA fuses with the
    gating products around it."""
    L, T = w.shape[0], u.shape[1]
    padded = jnp.pad(u.astype(jnp.float32), ((0, 0), (L - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    return sum(padded[:, j:j + T] * w[j] for j in range(L)).astype(u.dtype)


def _rope(x, positions, theta):
    """Rotary embedding over the whole head of x (B, T, H, D), the halves
    paired (x_i with x_(i + D/2)), in float32."""
    half = x.shape[-1] // 2
    freq = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                   / half)
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def yarn_inv_freq(dim, theta, factor, original_len, beta_fast, beta_slow):
    """YaRN's rotary frequencies over `dim` rotated lanes (dim / 2 of
    them), as numpy float64: 1 / theta^(2i/dim) (extrapolation) where a
    frequency turns more than beta_fast times over original_len positions,
    that over `factor` (interpolation) where it turns fewer than beta_slow
    times, a linear ramp over i between the two (Peng et al.,
    arXiv:2309.00071; the bounds floored and ceiled as the published code
    does)."""
    import numpy as np
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def turns_at(turns):
        return dim * math.log(original_len / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return extra / factor * ramp + extra * (1 - ramp)


def _rope_part(x, positions, rotary):
    """x (B, T, H, D) turned as `rotary` (Rotary) says: the first share of
    each head, halves paired, in float32."""
    import numpy as np
    rot = int(x.shape[-1] * rotary.share)
    if rotary.yarn is None and rot == x.shape[-1]:
        return _rope(x, positions, rotary.theta)
    scale = 1.0
    if rotary.yarn is None:
        freq = 1.0 / rotary.theta ** (np.arange(0, rot, 2) / rot)
    else:
        *bounds, scale = rotary.yarn
        freq = yarn_inv_freq(rot, rotary.theta, *bounds)
    angle = positions.astype(jnp.float32)[:, None] * \
        jnp.asarray(freq, jnp.float32)[None, :]
    cos, sin = (scale * f(angle)[:, None, :] for f in (jnp.cos, jnp.sin))
    x32 = x.astype(jnp.float32)
    x1, x2, rest = jnp.split(x32, [rot // 2, rot], axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1).astype(x.dtype)


class TransformerLM:
    def __init__(self, config: TransformerConfig):
        self.cfg = config
        self.mixers = tuple(config.mixers) or ("mha",) * config.n_layers
        if len(self.mixers) != config.n_layers or \
                set(self.mixers) - set(MIXERS):
            raise ValueError(f"mixers {self.mixers}: {config.n_layers} "
                             f"words of {MIXERS}")
        self.mlps = tuple(config.mlps) or ("dense",) * config.n_layers
        if len(self.mlps) != config.n_layers or set(self.mlps) - set(MLPS):
            raise ValueError(f"mlps {self.mlps}: {config.n_layers} words "
                             f"of {MLPS}")
        if "experts" in self.mlps and (config.experts is None
                                       or config.mlp != "swiglu"):
            raise ValueError("an \"experts\" layer wants `experts` (Experts) "
                             "and mlp=\"swiglu\"")
        if "gqa" in self.mixers and (len(config.gqa) != config.n_layers or any(
                not isinstance(g, GQA) for g, m in zip(config.gqa,
                                                       self.mixers)
                if m == "gqa")):
            raise ValueError(f"a \"gqa\" layer wants its GQA in `gqa`, one "
                             f"entry a layer: {config.gqa}")
        self.head_dim = config.head_dim or config.d_model // config.n_heads
        self.expert_layers = tuple(i for i, m in enumerate(self.mlps)
                                   if m == "experts")

    # -- parameters ---------------------------------------------------------
    def _shapes(self):
        """[(name, shape, fan_in)] in the order the seed is spent: a matrix
        is drawn normal / sqrt(fan_in); fan_in None is a norm's weight
        (ones), 0 a bias (zeros). Matrices are stored (in, out); a "conv"
        layer's taps (3, d) have a fan-in of 3."""
        cfg = self.cfg
        d, f, hd = cfg.d_model, cfg.d_ff, self.head_dim

        def norm(name):
            return [(name + "_g", (d,), None)] + (
                [(name + "_b", (d,), 0)] if cfg.norm == "layernorm" else [])
        out = [("embed", (cfg.vocab_size, d), d)]
        if cfg.learned_positions:
            out.append(("pos_embed", (cfg.max_len, d), d))
        for i, kind in enumerate(self.mixers):
            p = f"layer{i}_"
            out += norm(p + "ln1")
            if kind == "mha":
                out += [(p + w, (d, d), d) for w in ("wq", "wk", "wv", "wo")]
            elif kind == "gqa":
                layer = cfg.gqa[i]
                wide = layer.heads * hd
                kv = (cfg.n_kv_heads or cfg.n_heads) * hd
                out += [(p + "wq", (d, wide), d), (p + "wk", (d, kv), d),
                        (p + "wv", (d, kv), d)]
                if layer.gate:
                    out.append((p + "wg", (d, layer.heads), d))
                out.append((p + "wo", (wide, d), wide))
                if layer.qk_norm:
                    out += [(p + "q_norm_g", (hd,), None),
                            (p + "k_norm_g", (hd,), None)]
            elif kind == "conv":
                out += [(p + "w_bcx", (d, 3 * d), d),
                        (p + "conv_w", (3, d), 3), (p + "wo", (d, d), d)]
            else:
                kv = d if kind == "lightning" else \
                    (cfg.n_kv_heads or cfg.n_heads) * hd
                out += [(p + "wq", (d, d), d), (p + "wk", (d, kv), d),
                        (p + "wv", (d, kv), d), (p + "wg", (d, d), d)]
                if kind == "lightning":
                    out += [(p + "q_norm_g", (hd,), None),
                            (p + "k_norm_g", (hd,), None),
                            (p + "o_norm_g", (d,), None)]
                out.append((p + "wo", (d, d), d))
            out += norm(p + "ln2")
            if self.mlps[i] == "experts":
                ex = cfg.experts
                n, fe, fs = ex.held[1], ex.width, ex.shared_width
                # a held expert's gate and up projections side by side:
                # one grouped product takes both
                out += [(p + "router", (d, ex.count), d),
                        (p + "e_gate_in", (n, d, 2 * fe), d),
                        (p + "e_out", (n, fe, d), fe)]
                if ex.bias_rate is not None:
                    out.append((p + "e_bias", (ex.count,), 0))
                if fs:
                    out += [(p + "s_gate", (d, fs), d),
                            (p + "s_in", (d, fs), d),
                            (p + "s_out", (fs, d), fs)]
                continue
            if cfg.mlp == "swiglu":
                out.append((p + "w_gate", (d, f), d))
            out += [(p + "w_in", (d, f), d), (p + "w_out", (f, d), f)]
        out += norm("lnf")
        if not cfg.tied_head:
            out.append(("head", (cfg.vocab_size, d), d))
        return out

    def init_params(self, key):
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        # the split is what the dense models' seeds were drawn with; a
        # layer list with more matrices than it holds folds the rest in
        k = itertools.chain(
            jax.random.split(key, 4 + 8 * cfg.n_layers),
            (jax.random.fold_in(key, 1 << 20 | j) for j in itertools.count()))
        params = {}
        for name, shape, fan_in in self._shapes():
            if fan_in:
                params[name] = (jax.random.normal(next(k), shape, jnp.float32)
                                / math.sqrt(fan_in)).astype(dt)
            else:
                params[name] = (jnp.ones if fan_in is None else jnp.zeros)(
                    shape, jnp.float32 if _is_state(name) else dt)
        return params

    # -- forward ------------------------------------------------------------
    def _ln(self, x, g, b):
        m = jnp.mean(x.astype(jnp.float32), axis=-1, keepdims=True)
        v = jnp.var(x.astype(jnp.float32), axis=-1, keepdims=True)
        return ((x - m) * jax.lax.rsqrt(v + self.cfg.norm_eps)).astype(
            x.dtype) * g + b

    def _norm(self, x, params, name):
        if self.cfg.norm == "layernorm":
            return self._ln(x, params[name + "_g"], params[name + "_b"])
        with jax.named_scope("norm"):
            return _rms(x, params[name + "_g"], self.cfg.norm_eps)

    def _residual(self, x, y):
        return x + _scaled(y, self.cfg.residual_scale)

    def _block(self, params, prefix, x, sp_axis, tp_axis=None, mesh=None,
               positions=None):
        """One pre-norm block. Inside shard_map, attention/MLP weights may be
        Megatron-sharded over `tp_axis` (wq/wk/wv/w_in column-parallel,
        wo/w_out row-parallel): each device computes its local slice of heads
        / hidden units and a psum over tp after each row-parallel matmul
        restores the full residual stream. Head/hidden split is read off the
        *local* weight shapes, so the same code serves the unsharded path.
        `mesh` is the multi-device mesh of a pure-jit (GSPMD) caller: the
        flash kernel then runs per shard. A layer with experts returns
        (x, its routing counts)."""
        with jax.named_scope("attn"):
            x = self._residual(x, self._attn(params, prefix, x, sp_axis,
                                             tp_axis, mesh, positions))
        with jax.named_scope("mlp"):
            h = self._norm(x, params, prefix + "ln2")
            if self.mlps[_layer_of(prefix)] == "experts":
                if sp_axis is not None or tp_axis is not None:
                    raise NotImplementedError(
                        "an \"experts\" layer inside shard_map over sp / tp")
                y, counts = self._experts(params, prefix, h)
                return self._residual(x, checkpoint_name(y, "mlp_out")), \
                    counts
            if self.cfg.mlp == "swiglu":
                y = (jax.nn.silu(h @ params[prefix + "w_gate"])
                     * (h @ params[prefix + "w_in"])) \
                    @ params[prefix + "w_out"]
            else:
                y = jax.nn.gelu(h @ params[prefix + "w_in"]) \
                    @ params[prefix + "w_out"]
            if tp_axis is not None:
                y = jax.lax.psum(y, tp_axis)
            y = checkpoint_name(y, "mlp_out")
            return self._residual(x, y)

    def _experts(self, params, prefix, h):
        """The MLP of an "experts" layer on h (B, T, d): the routed part
        this chip's experts give (parallel/moe.moe_routed) plus the shared
        expert. Returns (y, moe_routed's counts)."""
        from ..parallel.moe import moe_routed
        ex = self.cfg.experts
        B, T, d = h.shape
        with jax.named_scope("moe"):
            y, counts = moe_routed(
                h.reshape(B * T, d), params[prefix + "router"],
                params[prefix + "e_gate_in"], params[prefix + "e_out"],
                held=tuple(ex.held), k=ex.per_token,
                rows=ex.rows or B * T * ex.per_token, score=ex.score,
                scaling=ex.scaling, norm_topk=ex.norm_topk,
                bias=params.get(prefix + "e_bias"))
            y = y.reshape(B, T, d)
            if ex.shared_width:
                with jax.named_scope("moe_shared"):
                    y = y + (jax.nn.silu(h @ params[prefix + "s_gate"])
                             * (h @ params[prefix + "s_in"])) \
                        @ params[prefix + "s_out"]
            return y, counts

    def _attn(self, params, prefix, x, sp_axis, tp_axis, mesh,
              positions=None):
        """The mixer half of a block: ln1, projections, the layer's mixer
        (TransformerConfig's table), the output projection (psum over tp
        where sharded). Returns attn_out."""
        B, T, D = x.shape
        hd = self.head_dim
        kind = self.mixers[_layer_of(prefix)]
        if kind != "mha" and (sp_axis is not None or tp_axis is not None):
            raise NotImplementedError(
                f"a {kind!r} layer inside shard_map over sp / tp")
        h = self._norm(x, params, prefix + "ln1")
        if kind == "conv":
            return checkpoint_name(self._conv(params, prefix, h), "attn_out")
        # head counts are read off the LOCAL weight shapes (D/tp columns
        # inside shard_map with TP)
        q, kk, v = ((h @ params[prefix + w]).reshape(B, T, -1, hd)
                    for w in ("wq", "wk", "wv"))
        layer = self.cfg.gqa[_layer_of(prefix)] if kind == "gqa" else None
        if kind == "lightning":
            attn = self._lightning(params, prefix, q, kk, v, positions)
        elif kind == "gqa":
            attn = self._gqa(params, prefix, layer, q, kk, v, mesh,
                             positions)
        elif kind == "sparse":
            with jax.named_scope("sparse_attn"):
                if T > self.cfg.select.dense_len:
                    attn = block_sparse_attention(q, kk, v, self.cfg.select)
                else:
                    attn = self._softmax_attention(q, kk, v, sp_axis, mesh)
        else:
            attn = self._softmax_attention(q, kk, v, sp_axis, mesh)
        if kind == "gqa" and layer.gate:       # a gate a head
            gate = h @ params[prefix + "wg"]
            with jax.named_scope("gate"):
                attn = attn * jax.nn.sigmoid(gate)[..., None]
        attn = attn.reshape(B, T, -1)
        if kind in ("sparse", "lightning"):
            gate = h @ params[prefix + "wg"]
            with jax.named_scope("gate"):
                attn = attn * jax.nn.sigmoid(gate)
        attn_out = attn @ params[prefix + "wo"]
        if tp_axis is not None:
            attn_out = jax.lax.psum(attn_out, tp_axis)
        return checkpoint_name(attn_out, "attn_out")

    def _lightning(self, params, prefix, q, k, v, positions):
        """QK-norm, rotary, the chunked decayed scan at 1 / sqrt(head_dim),
        RMSNorm over all heads of the result."""
        from ..parallel.linear_attention import (alibi_slopes,
                                                 lightning_attention)
        cfg = self.cfg
        B, T, H, hd = q.shape
        with jax.named_scope("norm"):
            q = _rms(q, params[prefix + "q_norm_g"], cfg.norm_eps)
            k = _rms(k, params[prefix + "k_norm_g"], cfg.norm_eps)
        with jax.named_scope("rope"):
            if positions is None:
                positions = jnp.arange(T)
            q, k = (_rope(x, positions, cfg.rope_theta) for x in (q, k))
        with jax.named_scope("lightning"):
            out = lightning_attention(q, k, v, alibi_slopes(H),
                                      scale=1.0 / math.sqrt(hd))
        with jax.named_scope("norm"):
            return _rms(out.reshape(B, T, H * hd),
                        params[prefix + "o_norm_g"], cfg.norm_eps)

    def _conv(self, params, prefix, h):
        """LFM2's gated short convolution on h (B, T, d): B, C, x~ =
        split(h W_bcx), y = (C * short_conv(B * x~)) W_o."""
        with jax.named_scope("short_conv"):
            b, c, xt = jnp.split(h @ params[prefix + "w_bcx"], 3, axis=-1)
            with jax.named_scope("short_conv_taps"):
                y = c * short_conv(b * xt, params[prefix + "conv_w"])
            return y @ params[prefix + "wo"]

    def _gqa(self, params, prefix, layer, q, k, v, mesh, positions):
        """QK-norm where the layer asks for it, rotary as its Rotary says,
        then causal softmax attention at 1 / sqrt(head_dim), inside the
        layer's window where it has one."""
        if layer.qk_norm:
            with jax.named_scope("norm"):
                q = _rms(q, params[prefix + "q_norm_g"], self.cfg.norm_eps)
                k = _rms(k, params[prefix + "k_norm_g"], self.cfg.norm_eps)
        with jax.named_scope("rope"):
            if positions is None:
                positions = jnp.arange(q.shape[1])
            q, k = (_rope_part(x, positions, layer.rotary) for x in (q, k))
        if layer.window is None:
            return self._softmax_attention(q, k, v, None, mesh)
        with jax.named_scope("window_attn"):
            return self._softmax_attention(q, k, v, None, mesh, layer.window)

    def _softmax_attention(self, q, kk, v, sp_axis, mesh, window=None):
        """Causal softmax attention of q (B, T, H, hd) over kk, v (B, T, H
        or fewer, hd): ring attention over `sp_axis`, else the flash
        kernels, else the dense reference. `window`: query i reads keys j
        with i - j < window."""
        if sp_axis is not None:
            return ring_attention(q, kk, v, sp_axis, causal=True)
        if self.cfg.flash_attention:
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
            if window is not None:
                attn_fn = functools.partial(attn_fn, window=window)
            if mesh is not None:
                # a Mosaic kernel cannot be partitioned automatically (jax
                # refuses at lowering): run it per shard. Attention is
                # independent per sequence and per head, which is exactly
                # how GSPMD lays q/k/v out — batch over dp, heads over tp.
                from ..parallel._compat import shard_map
                spec = P("dp" if "dp" in mesh.axis_names else None, None,
                         "tp" if "tp" in mesh.axis_names else None, None)
                attn_fn = shard_map(attn_fn, mesh, (spec,) * 3, spec)
            return attn_fn(q, kk, v)
        if kk.shape[2] != q.shape[2]:
            kk, v = (jnp.repeat(x, q.shape[2] // x.shape[2], axis=2)
                     for x in (kk, v))
        if window is not None:
            return attention_reference(q, kk, v, causal=True, window=window)
        return attention_reference(q, kk, v, causal=True)

    def apply(self, params, tokens, sp_axis=None, positions=None, tp_axis=None,
              mesh=None, counts=False):
        """tokens (B, T) int32 -> logits (B, T, vocab). When called inside a
        shard_map with a sequence axis, pass sp_axis and per-shard positions;
        pass tp_axis when attention/MLP weights are Megatron-sharded; pass
        the mesh when tracing a pure-jit program over several devices. With
        `counts`, (logits, the expert layers' routing: {"held_slots",
        "slots_over"}, a number an expert layer, and "experts" (layers, B *
        T, per_token), what each token chose; with an expert bias "load"
        (layers, count), the slots each expert drew)."""
        logits, routed = self._head(params, tokens, sp_axis, positions,
                                    tp_axis, mesh, counts)
        with jax.named_scope("logits"):
            logits = logits.astype(jnp.float32)
        return (logits, routed) if counts else logits

    def _head(self, params, tokens, sp_axis, positions, tp_axis, mesh,
              counts):
        """`apply`'s logits in the head's own dtype, and the expert layers'
        routing where `counts` asks for it (else None)."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = _scaled(params["embed"][tokens], cfg.embed_scale)
            if positions is None:
                positions = jnp.arange(tokens.shape[1])
            if cfg.learned_positions:
                x = x + params["pos_embed"][positions]
        if cfg.remat:
            block = jax.checkpoint(
                lambda p, pref, y: self._block(p, pref, y, sp_axis, tp_axis,
                                               mesh, positions),
                static_argnums=(1,), policy=_remat_policy(cfg.remat_policy))
        else:
            block = lambda p, pref, y: self._block(p, pref, y, sp_axis,
                                                   tp_axis, mesh, positions)
        routed = []
        for i in range(cfg.n_layers):
            with jax.named_scope(f"layer{i}"):
                x = block(params, f"layer{i}_", x)
                if i in self.expert_layers:
                    x, seen = x
                    routed.append(seen)
        with jax.named_scope("final_ln"):
            x = self._norm(x, params, "lnf")
        with jax.named_scope("logits"):
            head = params["embed" if cfg.tied_head else "head"]
            logits = _scaled(x, cfg.logit_scale) @ head.T
        if not counts:
            return logits, None
        return logits, {k: jnp.stack([seen[k] for seen in routed])
                        for k in routed[0]}

    def loss(self, params, tokens, targets, sp_axis=None, positions=None,
             tp_axis=None, mesh=None, counts=False):
        """Mean next-token negative log-likelihood; with `counts` (a model
        with an expert layer), (loss, apply's counts but "experts")."""
        # `forward`, `loss` and (in the train step) `optimizer` are the top
        # words a device trace is read by (PERF.md section 3)
        with jax.named_scope("forward"):
            logits, routed = self._head(params, tokens, sp_axis, positions,
                                        tp_axis, mesh, counts)
            if counts:      # the step's counters: a few numbers a layer
                routed = {k: v for k, v in routed.items() if k != "experts"}
        with jax.named_scope("loss"):
            # logsumexp less the target's logit, read off the head's own
            # logits: no log-softmax over the vocabulary, which XLA writes
            # whole as f32 (B, T, V) to gather one number a token from. A
            # bf16 logit is exact in f32, so the value is the same.
            lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
            picked = jnp.take_along_axis(logits, targets[..., None],
                                         axis=-1)[..., 0].astype(jnp.float32)
            nll = lse - picked
            return (jnp.mean(nll), routed) if counts else jnp.mean(nll)

    # -- sharded training ---------------------------------------------------
    def param_sharding(self, mesh, tp_axis="tp"):
        from ..parallel.tensor_parallel import transformer_param_specs
        has_tp = tp_axis in mesh.axis_names
        matrices = self._matrices()
        shd = {}
        for name in self._param_names():
            shd[name] = NamedSharding(
                mesh, transformer_param_specs(name, _FakeNd(2), tp_axis)
                if has_tp and name in matrices else P())
        return shd

    def _param_names(self):
        return [name for name, _, _ in self._shapes()]

    def _matrices(self):
        return {name for name, shape, _ in self._shapes() if len(shape) == 2}

    def make_train_step(self, mesh, lr=1e-3, use_sp=True, n_steps=None):
        """Fully-sharded train step: dp on batch, tp on weights, sp on
        sequence (ring attention through shard_map). Adam in fp32 master
        precision. Returns (step_fn, shard_params_fn, init_opt_fn);
        step_fn(params, opt_state, tokens, targets, step_i) -> (params,
        opt_state, loss) with params/opt_state donated.

        n_steps: compile a MULTI-step program — lax.scan of the step with
        params/opt carried on device, one dispatch for the whole window
        (the TrainStep.run_steps analog; per-step RNG/step_i advance in
        the scan).

        A model with an expert layer: step_fn returns (params, opt_state,
        loss, counts), counts the step's routing counts a layer
        ({"held_slots", "slots_over"}: TransformerLM.apply), which cost the
        step nothing it would not compute anyway. An expert layer with a
        bias (Experts.bias_rate) adds its "load": the step moves the bias
        by moe_balance from it, under `moe_balance`; the bias takes no
        gradient and has no optimizer state."""
        from .. import profiler as _prof
        from ..parallel._compat import shard_map
        from ..parallel.tensor_parallel import transformer_param_specs

        began = time.time()
        axis_names = mesh.axis_names
        has = {a: a in axis_names for a in ("dp", "tp", "sp")}
        sp_axis = "sp" if (use_sp and has["sp"]) else None

        matrices = self._matrices()

        def _is_matmul(n):
            return n in matrices and n not in ("embed", "pos_embed", "head")

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
                         if has["tp"] and n in matrices else P())
                     for n in self._param_names()}
        data_spec = P("dp" if has["dp"] else None,
                      sp_axis)

        model = self
        routed = bool(self.expert_layers)
        state = [n for n in pspec if _is_state(n)]
        if routed and (n_steps or sp_axis is not None):
            raise NotImplementedError("an expert layer's counts through a "
                                      "scan of steps or a shard_map over sp")
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
                              mesh=mesh if mesh.devices.size > 1 else None,
                              counts=routed)

        from ..parallel.train import _make_update_rule
        _, adam_rule = _make_update_rule("adam", lr, 0.0, 0.0, {})

        def step(params, opt_state, tokens, targets, step_i):
            fixed = {k: params[k] for k in state}
            loss, grads = jax.value_and_grad(
                lambda p, *xy: loss_fn({**p, **fixed}, *xy), has_aux=routed)(
                {k: v for k, v in params.items() if k not in fixed},
                tokens, targets)
            new_params, new_opt = {}, {}
            with jax.named_scope("optimizer"):
                t = step_i + 1
                for k, g in grads.items():
                    # fp32 master weights around the shared adam rule
                    w32, new_opt[k] = adam_rule(
                        params[k].astype(jnp.float32), g.astype(jnp.float32),
                        opt_state[k], t)
                    new_params[k] = w32.astype(params[k].dtype)
            if routed:
                loss, counts = loss
                if state:
                    from ..parallel.moe import moe_balance
                    with jax.named_scope("optimizer"), \
                            jax.named_scope("moe_balance"):
                        for j, layer in enumerate(self.expert_layers):
                            k = f"layer{layer}_e_bias"
                            new_params[k] = moe_balance(
                                fixed[k], counts["load"][j],
                                self.cfg.experts.bias_rate)
                return new_params, new_opt, loss, counts
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
        opt_sh = {n: (param_sh[n], param_sh[n]) for n in pspec
                  if n not in state}
        data_sh = NamedSharding(mesh, data_spec)
        # outputs pinned to the input layout: left to the compiler, a
        # replicated leaf can come back sharded and the next (donating)
        # call then refuses it
        jit_step = jax.jit(step,
                           in_shardings=(param_sh, opt_sh, data_sh, data_sh,
                                         None),
                           out_shardings=(param_sh, opt_sh, None)
                           + (None,) * routed,
                           donate_argnums=(0, 1))

        # the helpers are set-up: each call a row of profiler.setup_stats()
        def shard_params(params):
            # jnp.asarray copy first: device_put may alias the source buffer
            # (zero-copy on CPU), and the donated step would then delete the
            # caller's arrays with it
            with _prof.setup_span("shard_params", "TransformerLM"):
                return {k: jax.device_put(jnp.asarray(v).copy(),
                                          NamedSharding(mesh, pspec[k]))
                        for k, v in params.items()}

        def init_opt(params):
            # born with the step's layout: unplaced zeros would all land
            # on device 0 first and give the first call a signature (and a
            # trace) of its own
            with _prof.setup_span("init_opt", "TransformerLM"):
                return {k: (jnp.zeros(v.shape, jnp.float32,
                                      device=param_sh[k]),
                            jnp.zeros(v.shape, jnp.float32,
                                      device=param_sh[k]))
                        for k, v in params.items() if k not in state}

        _prof.setup_row("make_train_step", "TransformerLM", began,
                        time.time())
        return jit_step, shard_params, init_opt


class _FakeNd:
    def __init__(self, ndim):
        self.ndim = ndim
        self.shape = (1,) * ndim
