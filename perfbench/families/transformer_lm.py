"""A dense pre-norm language model through `models.transformer`:
`TransformerLM.make_train_step` (bfloat16 weights, remat, flash attention,
Adam with float32 moments), one dispatch a step."""
from __future__ import annotations

from .. import flops
from ..reference import compare, gpt2

# Largest |system - reference| logit over the largest |reference| logit on
# the sample. The system holds weights and activations in bfloat16 (8 bits
# of mantissa) through 24 blocks; measured 1.0e-2 and 1.2e-2 at full width
# (GPT-2 medium, 2 x 1,024 tokens, seeds 1-2, CPU rehearsal). Attention
# without the causal mask, or a block left out, moves logits by about
# their own size.
TOLERANCE = 5e-2


def train_flops_per_item(config, traffic):
    return flops.transformer_train_flops_per_item(config, traffic["seq_len"])


class TrainJob:
    def __init__(self, cell, seed, spans):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from incubator_mxnet_tpu.models.transformer import (
            TransformerConfig, TransformerLM)
        from incubator_mxnet_tpu.parallel import make_mesh
        config, mix = cell.config, cell.traffic
        self.config, self.seed, self.spans = config, seed, spans
        seq = mix["seq_len"]
        if seq > config["max_len"]:
            raise ValueError(f"seq_len {seq} exceeds the configuration's "
                             f"{config['max_len']} positions")
        batch = mix["batch_per_chip"] * cell.chips
        self.items_per_step = batch * seq
        self.model = TransformerLM(TransformerConfig(
            vocab_size=config["vocab_size"], d_model=config["d_model"],
            n_heads=config["n_heads"], n_layers=config["n_layers"],
            d_ff=config["d_ff"], max_len=config["max_len"],
            dtype=mix["dtype"], remat=mix["remat"], flash_attention=True))
        mesh = self.mesh = make_mesh({"dp": cell.chips},
                                     jax.devices()[:cell.chips])
        self.step, shard_params, init_opt = self.model.make_train_step(
            mesh, lr=mix["lr"], use_sp=False)
        key = jax.random.PRNGKey(seed)
        self.params = shard_params(jax.jit(self.model.init_params)(key))
        self.opt = init_opt(self.params)
        data = NamedSharding(mesh, P("dp", None))
        ring = jax.jit(
            lambda k: jax.random.randint(k, (mix["ring"], batch, seq), 0,
                                         config["vocab_size"], jnp.int32),
            out_shardings=NamedSharding(mesh, P(None, "dp", None)))(
                jax.random.fold_in(key, 1))
        self.tokens = [jax.device_put(ring[i], data)
                       for i in range(mix["ring"])]
        self.targets = [jnp.roll(t, -1, 1) for t in self.tokens]
        self.i = 0

    def dispatch(self):
        with self.spans("dispatch"):
            k = self.i % len(self.tokens)
            self.params, self.opt, loss = self.step(
                self.params, self.opt, self.tokens[k], self.targets[k],
                self.i)
            self.i += 1
            return loss

    def counters(self):
        return {}

    def check(self, n):
        """`TransformerLM.apply` (the step's forward pass: bfloat16, flash
        attention) against the reference on `n` seeded sequences of the
        cell's length."""
        import jax
        import jax.numpy as jnp
        seq = self.tokens[0].shape[1]
        tokens = jax.random.randint(
            jax.random.fold_in(jax.random.PRNGKey(self.seed), 2), (n, seq),
            0, self.config["vocab_size"], jnp.int32)
        mesh = self.mesh if self.mesh.devices.size > 1 else None
        got = jax.jit(lambda p, t: self.model.apply(p, t, mesh=mesh))(
            self.params, tokens)
        want = jax.jit(lambda p, t: gpt2.forward(p, t, self.config))(
            self.params, tokens)
        return compare(got, want, TOLERANCE,
                       f"{n} sequences of {seq} tokens, bfloat16 against "
                       "the float32 reference")

    def close(self):
        pass
