"""Laguna-S-2.1 through `models.transformer`: the same `TransformerLM` and
`make_train_step` as the dense family (bfloat16 weights, remat, the flash
kernels, Adam with float32 moments), with a layer list read from the
configuration: every layer the program's "gqa" mixer (its own count of query
heads, full or windowed, plain or YaRN rotary, a gate a head), `dense` MLPs
the program's SwiGLU, `sparse` ones its "experts" layer, told which experts
this chip holds. Token ring and dispatch are the dense family's; the step
hands back its routing counts beside the loss, which the job keeps on the
device and reads at the window's two marks (`counters`).

The comparison with the reference runs when the job is BUILT, on the freshly
drawn weights and before the optimizer's state and the step exist, as
`minicpm_sala.py` does it and for its reason. `check` hands the driver that
verdict.
"""
from __future__ import annotations

from ..cells import BenchError
from ..reference import laguna
from . import transformer_lm
from .minicpm_sala import draw_params

# The comparison has two limits, because a router's top-k is no continuous
# function of its input: the system routes by its own bfloat16 activations,
# the float32 reference by its own, and where a token's tenth and eleventh
# scores lie closer than bfloat16 tells apart the two choose differently,
# which moves that token's logits by a whole expert's output whatever the
# arithmetic. So (1) `relative_error`: the largest |system - reference| logit
# over the largest |reference| logit, the reference HANDED the system's
# choices (its weights stay its own float32 scores at those experts): what
# the arithmetic of every layer, the routed part's weights among it, gives;
# and (2) `slots_differing`: the share of all token slots (tokens x
# num_experts_per_tok x expert layers) whose expert the reference, routing
# by itself, did not choose for that token. PERF.md section 4 has the
# readings each limit lies between: the system's over its seeds, and the
# reference's own controls (window, gate, routed experts, YaRN dropped; the
# products in float8).
LIMITS = {"relative_error": 6e-2, "slots_differing": 4e-2}


def _rotary(rope):
    from incubator_mxnet_tpu.models.transformer import Rotary
    kind = rope["rope_type"]
    if kind not in ("default", "yarn"):
        raise ValueError(f"the program's rotary does not compute {kind!r}")
    return Rotary(
        theta=float(rope["rope_theta"]),
        share=float(rope["partial_rotary_factor"]),
        yarn=None if kind == "default" else (
            rope["factor"], rope["original_max_position_embeddings"],
            rope["beta_fast"], rope["beta_slow"], rope["attention_factor"]))


def model_config(config, mix):
    """The program's `TransformerConfig` for the configuration as run."""
    try:
        from incubator_mxnet_tpu.models.transformer import (
            GQA, Experts, TransformerConfig)
    except ImportError as e:    # a program from before PR 36
        raise BenchError("this checkout's TransformerLM has no \"gqa\" "
                         f"mixer or no expert layer: {e}") from e
    held = {"attention_bias": False, "gating": "per-head",
            "norm_topk_prob": True, "decoder_sparse_step": 1,
            "moe_apply_router_weight_on_input": False,
            "moe_router_logit_softcapping": 0,
            "tie_word_embeddings": False, "model_type": "laguna"}
    off = {k: config[k] for k, v in held.items() if config[k] != v}
    depth = config["num_hidden_layers"]
    kinds = config["layer_types"][:depth]
    mlps = config["mlp_layer_types"][:depth]
    off.update({k: sorted(set(config[k][:depth]) - ok) for k, ok in (
        ("layer_types", {"full_attention", "sliding_attention"}),
        ("mlp_layer_types", {"dense", "sparse"}),
        ("gating_types", {"per_head"}))
        if set(config[k][:depth]) - ok})
    if [i for i, m in enumerate(mlps) if m == "dense"] != \
            config["mlp_only_layers"]:
        off["mlp_only_layers"] = config["mlp_only_layers"]
    if off:
        raise ValueError(f"the program's layers do not compute {off}")
    own = config["experts_held"]
    if own["count"] != config["num_experts"]:
        raise ValueError(f"experts_held {own} and num_experts "
                         f"{config['num_experts']} disagree")
    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"], n_layers=depth,
        d_ff=config["intermediate_size"],
        max_len=config["max_position_embeddings"], dtype=mix["dtype"],
        remat=mix["remat"], flash_attention=True, mixers=("gqa",) * depth,
        norm="rmsnorm", norm_eps=config["rms_norm_eps"], mlp="swiglu",
        learned_positions=False, tied_head=False,
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        gqa=tuple(GQA(heads=heads,
                      window=config["sliding_window"]
                      if kind == "sliding_attention" else None,
                      rotary=_rotary(config["rope_parameters"][kind]))
                  for kind, heads in zip(
                      kinds, config["num_attention_heads_per_layer"])),
        mlps=tuple("experts" if m == "sparse" else "dense" for m in mlps),
        experts=Experts(
            count=config["published"]["num_experts"],
            held=(own["first"], own["count"]),
            per_token=config["num_experts_per_tok"],
            width=config["moe_intermediate_size"],
            shared_width=config["shared_expert_intermediate_size"],
            score="sigmoid", scaling=config["moe_routed_scaling_factor"],
            norm_topk=config["norm_topk_prob"],
            rows=mix.get("expert_rows", 0)))


def _layers(config):
    depth = config["num_hidden_layers"]
    return list(zip(config["layer_types"][:depth],
                    config["num_attention_heads_per_layer"][:depth],
                    config["mlp_layer_types"][:depth]))


def matmul_params(config, routed="held"):
    """Parameters a token multiplies. `routed`: "held" counts every held
    expert (what lies on the chip), "expected" counts an expert layer's
    routed part at the share of a token's slots that land here,
    num_experts_per_tok x held / published experts of ONE expert (10 x 8 /
    256 = 0.3125): what a token multiplies on average."""
    d, hd = config["hidden_size"], config["head_dim"]
    kv = config["num_key_value_heads"] * hd
    expert = 3 * d * config["moe_intermediate_size"]
    share = config["num_experts"] if routed == "held" else \
        config["num_experts_per_tok"] * config["num_experts"] \
        / config["published"]["num_experts"]
    total = config["vocab_size"] * d                    # the head's slice
    for _, heads, mlp in _layers(config):
        total += 2 * d * heads * hd + 2 * d * kv + d * heads
        if mlp == "dense":
            total += 3 * d * config["intermediate_size"]
        else:
            total += d * config["published"]["num_experts"] + share * expert \
                + 3 * d * config["shared_expert_intermediate_size"]
    return total


def train_flops_per_item(config, traffic):
    """Per token: 6 x the parameters a token multiplies, the routed experts
    at their expected share; causal attention as the dense family counts it,
    6 T H D a full layer (a token reads T / 2 keys on average), and 12 W H D
    a sliding one (a token reads W keys; the first W tokens' fewer are not
    taken off)."""
    seq, hd = traffic["seq_len"], config["head_dim"]
    window = config["sliding_window"]
    attn = sum(6 * seq * heads * hd if kind == "full_attention"
               else 12 * min(window, seq // 2) * heads * hd
               for kind, heads, _ in _layers(config))
    return 6 * matmul_params(config, "expected") + attn


def sample(config, seed, n, seq):
    """The `n` seeded sequences of `seq` tokens a comparison runs on."""
    import jax
    import jax.numpy as jnp
    return jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(seed), 2), (n, seq), 0,
        config["vocab_size"], jnp.int32)


def system_forward(model, params, tokens, mesh=None):
    """(logits, {layer: (B, T, k) the experts each token chose}) of
    `TransformerLM.apply`: the step's forward pass (bfloat16, the flash
    kernels full and windowed, the grouped experts)."""
    import jax
    got, routed = jax.jit(lambda p, t: model.apply(
        p, t, mesh=mesh, counts=True))(params, tokens)
    return got, {layer: routed["experts"][i].reshape(tokens.shape + (-1,))
                 for i, layer in enumerate(model.expert_layers)}


def readings(model, config, params, tokens, system, drop=()):
    """The comparison of `system` (system_forward's pair) on `tokens`, and
    its verdict: the system's logits against the reference handed the
    system's expert choices (`relative_error`), the share of slots the
    reference routing by itself chose otherwise (`slots_differing`, and
    `held_slots_differing` of the slots either put on a held expert), both
    against LIMITS (`ok`); and the error against that self-routed reference
    (`own_routing`: reported, not limited). `drop` hands the reference a
    control (benchmark/laguna_controls.py), never a cell's comparison: a
    control has to come out of this very function as not `ok`."""
    import jax.numpy as jnp
    got, mine = system
    own, theirs = laguna.forward(params, tokens, config, drop,
                                 with_choices=True)
    handed = laguna.forward(params, tokens, config, drop, choices=mine)
    first, count = model.cfg.experts.held
    slots = differing = held = held_differing = 0
    for layer, chosen in mine.items():
        other = theirs[layer]
        missed = ~jnp.any(chosen[..., :, None] == other[..., None, :], -1)
        here = lambda e: (e >= first) & (e < first + count)
        slots += chosen.size
        differing += int(jnp.sum(missed))
        # a slot put on a held expert by one and not by the other
        lost = ~jnp.any(other[..., :, None] == chosen[..., None, :], -1)
        held += int(jnp.sum(here(chosen)))
        held_differing += int(jnp.sum(missed & here(chosen))) + \
            int(jnp.sum(lost & here(other)))
    worst = lambda want: float(jnp.max(jnp.abs(got - want))
                               / jnp.max(jnp.abs(want)))
    read = {"relative_error": worst(handed), "own_routing": worst(own),
            "slots_differing": differing / max(slots, 1),
            "held_slots_differing": held_differing / max(held, 1),
            "slots": slots}
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

    def dispatch(self):
        with self.spans("dispatch"):
            k = self.i % len(self.tokens)
            self.params, self.opt, loss, routed = self.step(
                self.params, self.opt, self.tokens[k], self.targets[k],
                self.i)
            self.routed.append(routed)
            self.i += 1
            return loss

    def counters(self):
        """The steps' routing counts so far: `moe.slots_over` summed over
        steps and layers, `moe.held_slots` a layer's mean, and the buffer's
        `moe.expert_rows`. Read at the window's marks (a transfer of the
        numbers the steps left, no program)."""
        import jax
        import numpy as np
        for routed in jax.device_get(self.routed):
            self.seen["steps"] += 1
            self.seen["held"] += float(np.mean(routed["held_slots"]))
            self.seen["over"] += int(np.sum(routed["slots_over"]))
        self.routed = []
        steps = max(self.seen["steps"], 1)
        return {"moe.slots_over": self.seen["over"],
                "moe.held_slots": self.seen["held"] / steps,
                "moe.steps": self.seen["steps"],
                "moe.expert_rows": self.expert_rows}

    def check(self, n):
        return self.verdict
