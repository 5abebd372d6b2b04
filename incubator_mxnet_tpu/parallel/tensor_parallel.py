"""Tensor-parallel sharding specs.

The reference's only model parallelism is coarse layer placement
(`group2ctx`, src/executor/graph_executor.cc device-placement pass +
src/operator/cross_device_copy.cc). TPU-native TP is finer: weight matrices
are sharded over the "tp" mesh axis and XLA inserts the all-reduce after the
row-parallel matmul — Megatron-style column/row pairing expressed purely as
PartitionSpecs.
"""
from __future__ import annotations

from jax.sharding import PartitionSpec as P

__all__ = ["column_parallel_spec", "row_parallel_spec",
           "transformer_param_specs", "transformer_partition_rules"]


def column_parallel_spec(axis="tp"):
    """Weight (out, in) split on OUT dim -> each device computes a slice of
    the activations; no collective needed on forward."""
    return P(axis, None)


def row_parallel_spec(axis="tp"):
    """Weight (out, in) split on IN dim -> partial sums per device; XLA emits
    a psum over `axis` right after the matmul."""
    return P(None, axis)


def transformer_param_specs(name, value, tp_axis="tp"):
    """Megatron layout for models/transformer.py parameter names:
    qkv + mlp-in are column-parallel, attn-out + mlp-out row-parallel,
    embeddings split on vocab, everything else replicated."""
    nd = getattr(value, "ndim", len(getattr(value, "shape", ())))
    if nd < 2:
        return P()
    if any(t in name for t in ("wq", "wk", "wv", "wg", "w_in", "w_gate",
                               "wi")):
        return P(None, tp_axis)   # (d_model, d_head*H/tp) column
    if any(t in name for t in ("wo", "w_out")):
        return P(tp_axis, None)   # row parallel
    if "embed" in name or name == "head":
        return P(None, tp_axis)
    return P()


def transformer_partition_rules(tp_axis="tp"):
    """The same Megatron layout as a `match_partition_rules` table
    (first-match-wins regexes over models/transformer.py parameter
    names). Unlike the per-leaf spec fn, a table is *auditable*: the
    shardlint SL04 pass (and `on_unmatched="error"`) can prove total
    coverage, and the trailing explicit catch-all is the declared
    replicate-everything-else decision, not a silent fallback."""
    return [
        (r"(wq|wk|wv|wg|w_in|w_gate|wi)$", P(None, tp_axis)),   # column
        (r"(wo|w_out)$", P(tp_axis, None)),           # row parallel
        (r"(embed|^head)$", P(None, tp_axis)),        # embed, pos_embed, head
        (r".*", P()),   # layernorm scales/biases etc.: replicated
    ]
