"""LFM2-8B-A1B through `models.transformer`: the same `TransformerLM` and
`make_train_step` as the dense family (bfloat16 weights, remat, the flash
kernels, Adam with float32 moments), with a layer list read from the
configuration: `conv` layers the program's "conv" mixer (LFM2's gated short
convolution), `full_attention` layers its "gqa" mixer with QK-norm and no
output gate; the first `num_dense_layers` MLPs its SwiGLU, the others its
"experts" layer, told which experts this chip holds and carrying the expert
bias that selects them, which the step balances. The head is the embedding.
Token ring and dispatch are Laguna's family's; the step hands back its
routing counts and each expert's load beside the loss, which the job keeps
on the device and reads at the window's two marks (`counters`).

The comparison with the reference runs when the job is BUILT, on the freshly
drawn weights and before the optimizer's state and the step exist, as
`laguna.py` does it, with its two limits (a router's top-k is no continuous
function of its input): `relative_error`, the reference handed the system's
expert choices, and `slots_differing`, the share of token slots whose expert
the reference, routing by itself, did not choose. PERF.md section 4 has the
readings each limit lies between: the system's over its seeds, and the
reference's own controls (the taps dropped or reversed, QK-norm dropped, the
bias dropped, the products in float8).
"""
from __future__ import annotations

from ..cells import BenchError
from ..reference import lfm2_moe as reference
from . import laguna
from .laguna import sample, system_forward
from .minicpm_sala import draw_params

# Laguna's two limits (laguna.py says why two), `relative_error` tighter:
# the system reads 0.0189-0.0196 at 1 x 8,192 over four seeds, and QK-norm
# dropped from the one attention layer moves the logits by only 0.0582-0.0615
# (its q and k are near unit RMS already), so the limit lies between the
# two, 1.7x from each; the taps dropped or reversed read 1.27-1.44, the
# products in float8 0.287-0.290. `slots_differing`: the system 0.0136-0.0140,
# the float8 products 0.144-0.148, the bias dropped 0.32-0.35 (PERF.md
# section 4).
LIMITS = {"relative_error": 0.034, "slots_differing": 0.04}


def model_config(config, mix):
    """The program's `TransformerConfig` for the configuration as run; a
    `BenchError` at once where the program has no "conv" mixer."""
    from incubator_mxnet_tpu.models import transformer
    if "conv" not in getattr(transformer, "MIXERS", ()):
        raise BenchError("this checkout's TransformerLM has no \"conv\" "
                         "mixer (LFM2's gated short convolution)")
    from incubator_mxnet_tpu.models.transformer import (
        GQA, Experts, Rotary, TransformerConfig)
    held = {"conv_L_cache": 3, "conv_bias": False, "norm_topk_prob": True,
            "use_expert_bias": True, "tie_word_embeddings": True,
            "model_type": "lfm2_moe"}
    off = {k: config[k] for k, v in held.items() if config[k] != v}
    depth = config["num_hidden_layers"]
    kinds = reference.layers(config)
    strange = {k for k, _ in kinds} - {"conv", "full_attention"}
    if strange:
        off["layer_types"] = sorted(strange)
    if off:
        raise ValueError(f"the program's layers do not compute {off}")
    own = config["experts_held"]
    if own["count"] != config["num_experts"]:
        raise ValueError(f"experts_held {own} and num_experts "
                         f"{config['num_experts']} disagree")
    heads = config["num_attention_heads"]
    attention = GQA(heads=heads, rotary=Rotary(theta=float(
        config["rope_theta"])), qk_norm=True, gate=False)
    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=heads, n_layers=depth, d_ff=config["intermediate_size"],
        max_len=config["max_position_embeddings"], dtype=mix["dtype"],
        remat=mix["remat"], flash_attention=True,
        mixers=tuple("conv" if k == "conv" else "gqa" for k, _ in kinds),
        norm="rmsnorm", norm_eps=config["norm_eps"], mlp="swiglu",
        learned_positions=False, tied_head=True,
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // heads,
        gqa=tuple(attention if k == "full_attention" else None
                  for k, _ in kinds),
        mlps=tuple("dense" if dense else "experts" for _, dense in kinds),
        experts=Experts(
            count=config["published"]["num_experts"],
            held=(own["first"], own["count"]),
            per_token=config["num_experts_per_tok"],
            width=config["moe_intermediate_size"], score="sigmoid",
            scaling=float(config["routed_scaling_factor"]),
            norm_topk=config["norm_topk_prob"],
            rows=mix.get("expert_rows", 0),
            bias_rate=config["expert_bias_rate"]))


def matmul_params(config, routed="held"):
    """Parameters a token multiplies, the conv layers' taps among them (3
    multiply-adds a channel, as a weight each). `routed`: "held" counts
    every held expert (what lies on the chip), "expected" an expert layer's
    routed part at the share of a token's slots that land here,
    num_experts_per_tok x held / published experts of ONE expert (4 x 8 /
    32 = 1): what a token multiplies on average."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    kv = config["num_key_value_heads"] * d // heads
    expert = 3 * d * config["moe_intermediate_size"]
    share = config["num_experts"] if routed == "held" else \
        config["num_experts_per_tok"] * config["num_experts"] \
        / config["published"]["num_experts"]
    total = config["vocab_size"] * d                    # the head's slice
    for kind, dense in reference.layers(config):
        total += (4 * d * d + config["conv_L_cache"] * d if kind == "conv"
                  else 2 * d * d + 2 * d * kv)
        total += 3 * d * config["intermediate_size"] if dense else \
            d * config["published"]["num_experts"] + share * expert
    return total


def train_flops_per_item(config, traffic):
    """Per token: 6 x the parameters a token multiplies, the routed experts
    at their expected share; causal attention as the dense family counts
    it, 6 T H D a layer (a token reads T / 2 keys on average)."""
    layers = [k for k, _ in reference.layers(config)]
    return 6 * matmul_params(config, "expected") + \
        layers.count("full_attention") * 6 * traffic["seq_len"] \
        * config["hidden_size"]


def readings(model, config, params, tokens, system, drop=()):
    """The comparison of `system` (laguna.system_forward's pair) on
    `tokens`, and its verdict, as `laguna.readings` makes it against this
    family's reference: `relative_error` (the reference handed the system's
    choices), `slots_differing` (of all token slots, those whose expert the
    reference routing by itself did not choose), both against LIMITS
    (`ok`); `own_routing` (the error against that self-routed reference)
    reported, not limited. `drop` hands the reference a control
    (benchmark/lfm2_tool.py), never a cell's comparison."""
    import jax.numpy as jnp
    got, mine = system
    own, theirs = reference.forward(params, tokens, config, drop,
                                    with_choices=True)
    handed = reference.forward(params, tokens, config, drop, choices=mine)
    slots = differing = 0
    for layer, chosen in mine.items():
        missed = ~jnp.any(chosen[..., :, None] == theirs[layer][..., None, :],
                          -1)
        slots += chosen.size
        differing += int(jnp.sum(missed))
    worst = lambda want: float(jnp.max(jnp.abs(got - want))
                               / jnp.max(jnp.abs(want)))
    read = {"relative_error": worst(handed), "own_routing": worst(own),
            "slots_differing": differing / max(slots, 1), "slots": slots}
    return dict(read, ok=all(read[k] <= v for k, v in LIMITS.items()),
                limits=LIMITS)


def against_reference(model, params, config, seed, n, seq, mesh=None):
    """The verdict of the cell's comparison: `readings` of the step's own
    forward pass on the seed's sample."""
    tokens = sample(config, seed, n, seq)
    return dict(
        readings(model, config, params, tokens,
                 system_forward(model, params, tokens, mesh)),
        sample=f"{n} sequences of {seq} tokens, bfloat16 against the "
               "float32 reference, on the weights as drawn")


class TrainJob(laguna.TrainJob):
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
        self.expert_rows = self.model.cfg.experts.rows
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
        self.routed = []        # a step's counts, left on the device
        self.seen = {"steps": 0, "held": 0, "over": 0}
        self.balance = 0.0

    def counters(self):
        """Laguna's routing counters, and `moe.load_max_over_mean`: the
        most-loaded expert's slots over the mean, of all 32 experts, a mean
        over the expert layers and over every step dispatched so far."""
        import jax
        import numpy as np
        self.routed = jax.device_get(self.routed)       # one transfer
        for routed in self.routed:
            load = np.asarray(routed["load"], np.float64)
            self.balance += float(np.mean(load.max(1) / load.mean(1)))
        seen = super().counters()
        return dict(seen, **{"moe.load_max_over_mean":
                             self.balance / max(seen["moe.steps"], 1)})
