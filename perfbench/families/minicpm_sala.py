"""MiniCPM-SALA through `models.transformer`: the same `TransformerLM` and
`make_train_step` as the dense family (bfloat16 weights, remat, the flash
kernels, Adam with float32 moments), with a layer list read from the
configuration: `minicpm4` layers are the program's "sparse" mixer,
`lightning-attn` layers its "lightning" mixer. Token ring and dispatch are
the dense family's.

The comparison with the reference runs when the job is BUILT, on the
freshly drawn weights and before the optimizer's state and the step exist:
at 8,192 tokens and these widths the step's program and its state leave
about a GB of the chip, less than the float32 reference of one layer
needs. `check` hands the driver that verdict.
"""
from __future__ import annotations

import math

from ..reference import compare, minicpm_sala
from . import transformer_lm

KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning"}

# Largest |system - reference| logit over the largest |reference| logit on
# the sample. The system holds weights and activations in bfloat16 (8 bits
# of mantissa) through 4 blocks of 8,192 tokens; the reference is float32
# at `highest`. PERF.md section 4 has the readings the limit lies between:
# the system's over its seeds, and the reference's own with the decay
# dropped, with rotary dropped, and with the mixers' products in float8.
TOLERANCE = 3e-2


def model_config(config, mix):
    """The program's `TransformerConfig` for the configuration as run."""
    from incubator_mxnet_tpu.models.transformer import TransformerConfig
    from incubator_mxnet_tpu.parallel.sparse_attention import BlockSelect
    held = {"hidden_act": "silu", "attention_bias": False,
            "attn_use_rope": False, "attn_use_output_gate": True,
            "use_output_gate": True, "use_output_norm": True,
            "lightning_use_rope": True, "lightning_scale": "1/sqrt(d)",
            "qk_norm": True,
            "head_dim": config["hidden_size"] // config["num_attention_heads"],
            "lightning_nh": config["num_attention_heads"],
            "lightning_nkv": config["num_attention_heads"],
            "lightning_head_dim": config["head_dim"]}
    off = {k: config[k] for k, v in held.items() if config[k] != v}
    if off:
        raise ValueError(f"the program's mixers do not compute {off}")
    sp = config["sparse"]
    depth = config["num_hidden_layers"]
    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"], n_layers=depth,
        d_ff=config["intermediate_size"],
        max_len=config["max_position_embeddings"], dtype=mix["dtype"],
        remat=mix["remat"], flash_attention=True,
        mixers=tuple(KINDS[m] for m in config["mixer_types"][:depth]),
        norm="rmsnorm", norm_eps=config["rms_norm_eps"], mlp="swiglu",
        learned_positions=False, tied_head=config["tie_word_embeddings"],
        n_kv_heads=config["num_key_value_heads"],
        rope_theta=float(config["rope_theta"]),
        select=BlockSelect(
            kernel=sp["kernel_size"], stride=sp["kernel_stride"],
            block=sp["block_size"], topk=sp["topk"],
            init_blocks=sp["init_blocks"], window=sp["window_size"],
            dense_len=sp["dense_len"]),
        embed_scale=float(config["scale_emb"]),
        residual_scale=config["scale_depth"] / math.sqrt(
            config["published"]["num_hidden_layers"]),
        logit_scale=config["dim_model_base"] / config["hidden_size"])


def matmul_params(config):
    """Parameters a token multiplies: each layer's mixer and gated MLP and
    the output head's slice (the embedding is a look-up)."""
    d, f = config["hidden_size"], config["intermediate_size"]
    wide = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    mixer = {"minicpm4": 3 * d * wide + 2 * d * kv,
             "lightning-attn": 5 * d * wide}
    layers = config["mixer_types"][:config["num_hidden_layers"]]
    return sum(mixer[m] + 3 * d * f for m in layers) + \
        config["vocab_size"] * d


def train_flops_per_item(config, traffic):
    """Per token: 6 N for the matrix products; a `minicpm4` layer's causal
    attention on its dense path as the dense family counts it (6 T d a
    layer, d the width of all heads); a lightning layer's recurrence, 4 H
    d_head^2 forward (state update and read-out, whatever chunking
    implements them) and twice that backward."""
    seq = traffic["seq_len"]
    if seq > config["sparse"]["dense_len"]:
        raise ValueError("no count of a selected-block layer's work yet: "
                         "no cell runs beyond dense_len")
    heads, hd = config["num_attention_heads"], config["head_dim"]
    layers = config["mixer_types"][:config["num_hidden_layers"]]
    return 6 * matmul_params(config) + sum(
        6 * seq * heads * hd if m == "minicpm4" else 3 * 4 * heads * hd * hd
        for m in layers)


def draw_params(model, key):
    """`init_params`, with the norms' weights off their identity (1 +
    normal / 10), so that a forward pass that dropped one would show."""
    import jax
    params = model.init_params(key)
    keys = iter(jax.random.split(jax.random.fold_in(key, 3), len(params)))
    return {k: v + (0.1 * jax.random.normal(next(keys), v.shape)
                    ).astype(v.dtype) if v.ndim == 1 else v
            for k, v in sorted(params.items())}


def against_reference(model, params, config, seed, n, seq, mesh=None,
                      drop=()):
    """`TransformerLM.apply` (the step's forward pass: bfloat16, the flash
    kernels, the chunked scan) against the reference on `n` seeded
    sequences of `seq` tokens. `drop` hands the reference a control
    (benchmark/sala_controls.py), never a cell's comparison."""
    import jax
    import jax.numpy as jnp
    tokens = jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(seed), 2), (n, seq), 0,
        config["vocab_size"], jnp.int32)
    got = jax.jit(lambda p, t: model.apply(p, t, mesh=mesh))(params, tokens)
    want = minicpm_sala.forward(params, tokens, config, drop)
    return compare(got, want, TOLERANCE,
                   f"{n} sequences of {seq} tokens, bfloat16 against the "
                   "float32 reference, on the weights as drawn")


class TrainJob(transformer_lm.TrainJob):
    def __init__(self, cell, seed, spans):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from incubator_mxnet_tpu.models.transformer import TransformerLM
        from incubator_mxnet_tpu.parallel import make_mesh
        config, mix = cell.config, cell.traffic
        self.config, self.seed, self.spans = config, seed, spans
        seq, batch = mix["seq_len"], mix["batch_per_chip"] * cell.chips
        self.items_per_step = batch * seq
        self.model = TransformerLM(model_config(config, mix))
        mesh = self.mesh = make_mesh({"dp": cell.chips},
                                     jax.devices()[:cell.chips])
        self.step, shard_params, init_opt = self.model.make_train_step(
            mesh, lr=mix["lr"], use_sp=False)
        key = jax.random.PRNGKey(seed)
        self.params = shard_params(jax.jit(
            lambda k: draw_params(self.model, k))(key))
        self.verdict = against_reference(
            self.model, self.params, config, seed, mix["check_items"], seq,
            mesh if cell.chips > 1 else None)
        self.opt = init_opt(self.params)
        ring = jax.jit(
            lambda k: jax.random.randint(k, (mix["ring"], batch, seq), 0,
                                         config["vocab_size"], jnp.int32),
            out_shardings=NamedSharding(mesh, P(None, "dp", None)))(
                jax.random.fold_in(key, 1))
        data = NamedSharding(mesh, P("dp", None))
        self.tokens = [jax.device_put(ring[i], data)
                       for i in range(mix["ring"])]
        self.targets = [jnp.roll(t, -1, 1) for t in self.tokens]
        self.i = 0

    def check(self, n):
        return self.verdict
