"""2-bit gradient compression: bit-packed wire format + quantized
collectives.

Reference: src/kvstore/gradient_compression.cc:44-60 +
gradient_compression-inl.h CUDA kernels (2-bit stochastic-sign
quantization with error-feedback residual, packed 16 values per uint32
for the PS wire) and the server's DataHandleCompressed
(kvstore_dist_server.h:602).

TPU-native design: the pack/unpack are vectorized bit ops (XLA fuses
them); the fused quantize+residual+pack hot path is also provided as a
Pallas kernel (TPU Mosaic; interpreter elsewhere) per the accelerator
guide's "fuse what the compiler won't" rule. The collective is
`quantized_psum`: each shard packs its block (16x fewer wire bytes),
`all_gather`s the packed payload over the axis, and dequantize-sums
locally — a QSGD-style all-reduce with one quantization error per
contributor, carried forward by the residual.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ._compat import shard_map

__all__ = ["two_bit_pack", "two_bit_unpack", "quantize_pack",
           "quantize_pack_pallas", "quantized_psum", "quantized_allreduce"]

_GROUP = 16      # 2 bits x 16 values per uint32


def _codes(c, threshold):
    # 0 -> 0, +threshold -> 1, -threshold -> 2 (the reference's 2-bit states)
    return jnp.where(c >= threshold, jnp.uint32(1),
                     jnp.where(c <= -threshold, jnp.uint32(2),
                               jnp.uint32(0)))


def two_bit_pack(c, threshold):
    """Flat float array -> uint32 array of ceil(n/16) packed codes."""
    flat = c.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % _GROUP
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    codes = _codes(flat, threshold).reshape(-1, _GROUP)
    shifts = (jnp.arange(_GROUP, dtype=jnp.uint32) * 2)[None, :]
    return jnp.sum(codes << shifts, axis=1, dtype=jnp.uint32)


def two_bit_unpack(packed, n, threshold, dtype=jnp.float32):
    """Inverse of two_bit_pack: uint32 codes -> flat (n,) float array."""
    shifts = (jnp.arange(_GROUP, dtype=jnp.uint32) * 2)[None, :]
    codes = (packed[:, None] >> shifts) & jnp.uint32(3)
    vals = jnp.where(codes == 1, jnp.asarray(threshold, dtype),
                     jnp.where(codes == 2, jnp.asarray(-threshold, dtype),
                               jnp.asarray(0, dtype)))
    return vals.reshape(-1)[:n]


def quantize(g, residual, threshold):
    """THE 2-bit quantization rule (single source of truth — the kvstore
    push path, the packed wire, and the Pallas kernel all call this):
    c = g + residual; q = sign(c)*threshold where |c| >= threshold else 0;
    returns (q, new_residual = c - q)."""
    c = g + residual
    q = jnp.where(c >= threshold, threshold,
                  jnp.where(c <= -threshold, -threshold, 0.0)
                  ).astype(c.dtype)
    return q, c - q


def quantize_pack(g, residual, threshold):
    """Error-feedback quantize + pack in one step:
    returns (packed uint32, new_residual) with new_residual = c - q."""
    c = g.reshape(-1) + residual.reshape(-1)
    _, new_res = quantize(c, jnp.zeros_like(c), threshold)
    return two_bit_pack(c, threshold), new_res.reshape(g.shape)


# ---------------------------------------------------------------------------
# Pallas fused kernel: quantize + residual + pack one (rows, 2048) tile at
# a time — 2048 floats in, 128 uint32 out per row (VPU lane-width friendly).
# ---------------------------------------------------------------------------

_TILE = 2048


def _qp_kernel(g_ref, r_ref, thr_ref, packed_ref, newr_ref):
    # blocks are (rows, 16, 128): plane k holds code bit-pair k of each of
    # the row's 128 packed words. Packing is a static 16-step loop over
    # full-lane (rows, 128) slices — no reshape, no minor-dim reduction,
    # no unsigned arithmetic, all of which Mosaic refuses to lower.
    g = g_ref[...]
    r = r_ref[...]
    t = thr_ref[0, 0]
    _, newr_ref[...] = quantize(g, r, t)
    c = g + r
    acc = jnp.zeros(c.shape[:1] + c.shape[2:], jnp.int32)
    for k in range(_GROUP):
        ck = c[:, k, :]
        code = jnp.where(ck >= t, 1, jnp.where(ck <= -t, 2, 0))
        acc = acc | (code << (2 * k))
    packed_ref[...] = acc.astype(jnp.uint32)


def quantize_pack_pallas(g, residual, threshold, block_rows=8):
    """Pallas version of quantize_pack (interpret mode off-TPU); the packed
    wire bytes are identical to two_bit_pack's. Internally the flat input is
    padded to (rows, 2048) tiles and pre-transposed (by XLA, outside the
    kernel) to (rows, 16, 128) so that element [i, k, l] is flat
    [i*2048 + l*16 + k] — the kernel then packs lane-wise."""
    from jax.experimental import pallas as pl

    shape = g.shape
    flat = g.reshape(-1)
    res = residual.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % _TILE
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        res = jnp.concatenate([res, jnp.zeros((pad,), res.dtype)])
    rows = flat.shape[0] // _TILE
    lanes = _TILE // _GROUP
    gr = flat.reshape(rows, lanes, _GROUP).swapaxes(1, 2)
    rr = res.reshape(rows, lanes, _GROUP).swapaxes(1, 2)
    grid = (max(1, (rows + block_rows - 1) // block_rows),)
    br = min(block_rows, rows)
    thr = jnp.asarray([[threshold]], gr.dtype)
    interpret = jax.default_backend() != "tpu"
    if interpret:
        thr_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    else:
        # scalar operands must live in SMEM on TPU — Mosaic cannot lower a
        # direct load from an ANY-space ref
        from jax.experimental.pallas import tpu as pltpu
        thr_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    packed, newr = pl.pallas_call(
        _qp_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((br, _GROUP, lanes), lambda i: (i, 0, 0)),
                  pl.BlockSpec((br, _GROUP, lanes), lambda i: (i, 0, 0)),
                  thr_spec],
        out_specs=[pl.BlockSpec((br, lanes), lambda i: (i, 0)),
                   pl.BlockSpec((br, _GROUP, lanes), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, lanes), jnp.uint32),
                   jax.ShapeDtypeStruct((rows, _GROUP, lanes), gr.dtype)],
        interpret=interpret,
    )(gr, rr, thr)
    newr = newr.swapaxes(1, 2).reshape(-1)[:n].reshape(shape)
    return packed.reshape(-1)[: (n + _GROUP - 1) // _GROUP], newr


# ---------------------------------------------------------------------------
# Quantized collective
# ---------------------------------------------------------------------------

def quantized_psum(x, axis_name, threshold, residual):
    """Inside shard_map: all-reduce with a 2-bit wire format. Each member
    quantizes (with its own error-feedback residual), all_gathers the
    PACKED payload (1/16 of the float bytes over ICI/DCN), and
    dequantize-sums locally. Returns (sum, new_residual)."""
    n = x.size
    packed, new_res = quantize_pack(x, residual, threshold)
    allp = lax.all_gather(packed, axis_name)             # (W, ceil(n/16))
    deq = jax.vmap(lambda p: two_bit_unpack(p, n, threshold, x.dtype))(allp)
    return jnp.sum(deq, axis=0).reshape(x.shape), new_res


@functools.lru_cache(maxsize=32)
def _allreduce_fn(mesh, axis, threshold):
    """One jitted program per (mesh, axis, threshold): an un-jitted
    shard_map over a fresh closure retraces and recompiles on every
    call."""
    from jax.sharding import PartitionSpec as P

    def inner(xx, rr):
        return quantized_psum(xx, axis, threshold, rr)

    return jax.jit(shard_map(inner, mesh, in_specs=(P(), P()),
                             out_specs=(P(), P())))


def quantized_allreduce(x, mesh, threshold, residual=None, axis=None):
    """Whole-array entry: replicated x (and residual) -> (sum over the
    axis members' quantized contributions, new residual). With a
    replicated input every member contributes the same value — the
    multi-process kvstore instead passes per-process values via its
    collective mesh (kvstore._axis0_packed_sum)."""
    if residual is None:
        residual = jnp.zeros_like(x)
    fn = _allreduce_fn(mesh, axis or mesh.axis_names[0], float(threshold))
    return fn(x, residual)
