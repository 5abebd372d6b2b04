"""Mixture-of-Experts with expert parallelism (ep).

ABSENT in the reference (SURVEY §2.3 lists EP as a first-class TPU goal
beyond parity). Design: expert WEIGHTS are sharded over an `ep` mesh
axis; gating/dispatch run replicated (tokens are replicated across ep —
token sharding composes via a separate dp axis), each shard computes its
expert slice, and outputs are all-gathered for the combine. This shards
the dominant cost (expert FFN weights + matmuls) across the axis; the
GShard-style all_to_all token exchange, which additionally shards the
dispatch/combine tensors, is the token-sharded extension and is not
implemented here.

Capacity discipline keeps shapes static for XLA: each expert processes at
most `capacity` tokens; overflow tokens are dropped (their combine weight
is 0), matching Switch-Transformer semantics.
"""
from __future__ import annotations

import collections
import threading

import jax
import jax.numpy as jnp
from jax import lax

from ._compat import shard_map
from .flash_attention import _interpret

__all__ = ["moe_gate", "moe_apply", "moe_apply_a2a", "moe_sharded",
           "init_moe_params", "moe_route", "moe_dispatch", "moe_routed",
           "moe_balance", "grouped_matmul", "grouped_stats"]


def moe_gate(x, wg, k=1, capacity_factor=1.25):
    """Top-k gating (Switch for k=1). x: (N, d); wg: (d, E).
    Returns (dispatch (N, E, C) one-hot, combine (N, E, C) weights,
    aux_loss) with C = capacity."""
    N, _ = x.shape
    E = wg.shape[1]
    logits = (x.astype(jnp.float32) @ wg.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)            # (N, E)
    C = int(max(1, capacity_factor * k * N / E))

    dispatch = jnp.zeros((N, E, C), jnp.bool_)
    combine = jnp.zeros((N, E, C), jnp.float32)
    remaining = probs
    # queue positions are CUMULATIVE across the k rounds — restarting the
    # count per round would assign two tokens the same (expert, slot) and
    # sum their inputs in the expert queue
    counts = jnp.zeros((E,), jnp.int32)
    for _ in range(k):
        choice = jnp.argmax(remaining, axis=-1)        # (N,)
        gate = jnp.take_along_axis(remaining, choice[:, None], 1)[:, 0]
        onehot = jax.nn.one_hot(choice, E, dtype=jnp.int32)
        # position of each token within its expert's queue, offset by the
        # slots already consumed in earlier rounds
        pos = counts[None, :] + jnp.cumsum(onehot, axis=0) - onehot  # (N,E)
        in_cap = (pos < C) & onehot.astype(bool)
        pos_c = jnp.clip(pos, 0, C - 1)
        slot = jax.nn.one_hot(pos_c, C, dtype=jnp.bool_) & \
            in_cap[..., None]                           # (N, E, C)
        dispatch = dispatch | slot
        combine = combine + slot.astype(jnp.float32) * gate[:, None, None]
        remaining = remaining * (1.0 - onehot)
        counts = counts + jnp.sum(onehot, axis=0)
    # load-balancing aux loss (Switch eq. 4): E * sum_e f_e * P_e
    f = jnp.mean((probs == jnp.max(probs, -1, keepdims=True)).astype(
        jnp.float32), axis=0)
    P = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f * P)
    return dispatch, combine, aux


def moe_apply(x, params, axis_name=None, k=1, capacity_factor=1.25,
              activation=jax.nn.gelu):
    """One MoE FFN layer. x: (N, d). params: dict with
    wg (d, E), w1 (E_local, d, dff), w2 (E_local, dff, d).

    With axis_name (inside shard_map): E = E_local * ep_size; each shard
    builds only ITS experts' input queues (gating is replicated, the
    dispatch tensor is sliced to the local expert block before the queue
    einsum), runs its expert FFNs, and all-gathers the expert outputs for
    the replicated combine. Without axis_name: E = E_local (dense
    single-shard MoE, the numeric oracle)."""
    wg, w1, w2 = params["wg"], params["w1"], params["w2"]
    N, d = x.shape
    ep = 1 if axis_name is None else lax.psum(1, axis_name)
    e_local = w1.shape[0]
    E = e_local * ep

    dispatch, combine, aux = moe_gate(x, wg, k=k,
                                      capacity_factor=capacity_factor)
    C = dispatch.shape[-1]
    if axis_name is not None:
        # slice dispatch to the local expert block FIRST so the queue
        # einsum costs O(N * e_local * C * d) per shard, not O(N * E * C * d)
        r = lax.axis_index(axis_name)
        local_disp = lax.dynamic_slice_in_dim(dispatch, r * e_local,
                                              e_local, axis=1)  # (N, e_l, C)
        local_in = jnp.einsum("nec,nd->ecd", local_disp.astype(x.dtype), x)
        h = activation(jnp.einsum("ecd,edf->ecf", local_in, w1))
        local_out = jnp.einsum("ecf,efd->ecd", h, w2)   # (e_local, C, d)
        out = lax.all_gather(local_out, axis_name, axis=0,
                             tiled=True)                # (E, C, d)
    else:
        expert_in = jnp.einsum("nec,nd->ecd", dispatch.astype(x.dtype), x)
        h = activation(jnp.einsum("ecd,edf->ecf",
                                  expert_in.reshape(e_local, C, d), w1))
        out = jnp.einsum("ecf,efd->ecd", h, w2).reshape(E, C, d)
    y = jnp.einsum("nec,ecd->nd", combine.astype(out.dtype), out)
    return y, aux


def moe_apply_a2a(x, params, axis_name, k=1, capacity_factor=1.25,
                  activation=jax.nn.gelu):
    """GShard-style token-sharded MoE — the all-to-all dispatch variant.

    Run INSIDE shard_map with BOTH tokens and experts sharded over
    `axis_name` (in a composed mesh this is the `ep` axis, or the `dp`
    axis when experts ride the data-parallel groups, the GShard layout).

    x: (N_local, d) — THIS shard's tokens. params as in moe_apply with
    w1/w2 holding the local e_local = E/ep expert slices.

    Wire pattern (all shapes static):
      1. local top-k gating against the full E-expert router (wg is
         replicated) with per-shard capacity C,
      2. build per-(expert, slot) queues from local tokens:
         (E, C, d) = dispatch^T @ x,
      3. `all_to_all` over the EXPERT dim: each shard keeps its e_local
         experts' queues from every peer -> (ep * C) slots per local
         expert,
      4. run the local expert FFNs,
      5. `all_to_all` back (transpose of 3), combine locally.

    The backward schedule is the transpose: autodiff turns each
    all_to_all into the reverse all_to_all, so expert-weight grads stay
    shard-local and token grads return to their home shard — no psum over
    `axis_name` is needed for expert weights (and none must be applied:
    they are sharded, not replicated, over this axis).

    Returns (y (N_local, d), aux_loss). Numerics match moe_apply run
    independently on each shard's tokens with the full expert set.
    """
    wg, w1, w2 = params["wg"], params["w1"], params["w2"]
    N, d = x.shape
    ep = lax.psum(1, axis_name)
    e_local = w1.shape[0]
    E = e_local * ep

    dispatch, combine, aux = moe_gate(x, wg, k=k,
                                      capacity_factor=capacity_factor)
    C = dispatch.shape[-1]
    # 2. per-expert queues of MY tokens: (E, C, d)
    queues = jnp.einsum("nec,nd->ecd", dispatch.astype(x.dtype), x)
    # 3. exchange: split the E dim across shards, concat peers' blocks.
    # After this, shard r holds (ep, e_local, C, d): peer p's queue for
    # my experts [r*e_local, (r+1)*e_local).
    queues = queues.reshape(ep, e_local, C, d)
    queues = lax.all_to_all(queues, axis_name, split_axis=0, concat_axis=0,
                            tiled=False)
    # 4. local expert FFN over every peer's slots at once
    h = activation(jnp.einsum("pecd,edf->pecf", queues, w1))
    out = jnp.einsum("pecf,efd->pecd", h, w2)          # (ep, e_local, C, d)
    # 5. route results back to the token-home shards (transpose of 3)
    out = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                         tiled=False)
    out = out.reshape(E, C, d)
    y = jnp.einsum("nec,ecd->nd", combine.astype(out.dtype), out)
    return y, aux


def init_moe_params(key, d, dff, n_experts, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    scale = 1.0 / jnp.sqrt(d)
    return {
        "wg": (jax.random.normal(k1, (d, n_experts)) * scale).astype(dtype),
        "w1": (jax.random.normal(k2, (n_experts, d, dff)) * scale
               ).astype(dtype),
        "w2": (jax.random.normal(k3, (n_experts, dff, d)) *
               (1.0 / jnp.sqrt(dff))).astype(dtype),
    }


def moe_sharded(x, params, mesh, axis="ep", k=1, capacity_factor=1.25):
    """Whole-layer entry: w1/w2 sharded over `axis` on their expert dim,
    wg and x replicated. One compiled program; the only collective is the
    expert-output all_gather before the combine (see module docstring)."""
    from jax.sharding import PartitionSpec as P

    spec_p = {"wg": P(), "w1": P(axis), "w2": P(axis)}

    def inner(params, xx):
        return moe_apply(xx, params, axis_name=axis, k=k,
                         capacity_factor=capacity_factor)

    return shard_map(inner, mesh, in_specs=(spec_p, P()),
                     out_specs=(P(), P()))(params, x)


# -- the expert layer a chip of an expert-parallel deployment runs ----------
# Told which experts it holds, it routes every token over ALL the experts the
# router knows, and computes the part of the layer's result that its own
# experts give: what the experts held elsewhere would add is left out (on
# one chip the layer runs without its exchange). Token slots are sorted by
# local expert and the first `rows` of them gathered into ONE buffer for the
# chip: no capacity an expert, nothing dropped by expert; a step whose held
# slots exceed `rows` is counted (`slots_over`), never silent. Shapes, `rows`
# and the grouped products' cost do not depend on where the tokens went: the
# buffer's spare rows go through the last held expert at weight zero.
# docs/architecture/note_moe_layer.md has the contract; `moe_gate` above stays
# as the one-hot oracle the tests hold this to.

SCORES = ("sigmoid", "softmax")


# The rows a group averages in the buffer from which 512-row tiles beat 256:
# they fetch each matrix tile half as often, and cost at most one partly
# masked tile more a group. The race on the chip (benchmark/moe_race.py;
# PERF.md section 6) had 256 ahead by 4% at 512 rows a group, 512
# ahead by 6% at 1,536 and by 21% at 9,216.
_ROWS_512 = 1024


def _gmm_tiling(m, k, n, groups):
    """megablox tiles (rows, contraction, columns) for ONE call of m rows, a
    k-deep contraction and n columns over `groups` matrices, from that
    call's own shape. A width takes the largest multiple of 128, at most
    1,024, that divides it (1,792 and 3,584: 896), so no tile is masked; a
    width no such tile divides is one tile. Rows: 512 where a group
    averages `_ROWS_512` rows or more, else 256. The chip's scoped memory
    refuses matrix tiles beyond 1,024 x 1,024, and 1,024-row tiles wherever
    the contraction takes more than one step (benchmark/moe_race.py)."""
    most = 512 if m >= _ROWS_512 * groups else 256
    rows = next((t for t in (most, 256, 128) if m % t == 0), m)
    return rows, _width(k), _width(n)


def _width(size):
    """The largest multiple of 128, at most 1,024, that divides `size`;
    `size` itself where none does."""
    return next((t for t in range(1024, 0, -128) if size % t == 0), size)


# Grouped-product calls traced, by tiling, and the grid steps each takes a
# row tile of its buffer (contraction tiles x column tiles): the grid runs
# them for every row tile a group touches. Counted at trace time.
_grouped = {"row_tile_steps": 0, "tiles": collections.Counter()}
_grouped_lock = threading.Lock()


def grouped_stats():
    """{"calls": n, "row_tile_steps": n, "tiles": {(rows, k, n) tiles:
    calls}}: the megablox `gmm` and `tgmm` calls traced (forward, the
    matrices' transposes for the rows' gradient, and `tgmm` for the
    matrices'), and the sum of their grid steps a row tile."""
    with _grouped_lock:
        return {"calls": sum(_grouped["tiles"].values()),
                "row_tile_steps": _grouped["row_tile_steps"],
                "tiles": dict(_grouped["tiles"])}


def _tiled(m, k, n, groups):
    tiling = _gmm_tiling(m, k, n, groups)
    with _grouped_lock:
        _grouped["tiles"][tiling] += 1
        _grouped["row_tile_steps"] += -(-k // tiling[1]) * -(-n // tiling[2])
    return tiling


def _low(dtype):
    """The context a Pallas product of bfloat16 operands is traced in:
    Mosaic refuses the process's ambient `highest` (runtime.py) for them,
    in the backward pass as in the forward."""
    import contextlib
    return jax.default_matmul_precision("default") \
        if dtype == jnp.bfloat16 else contextlib.nullcontext()


def _megablox_backend():
    """The module of the kernels themselves (the package's `gmm` is its
    own custom_vjp over them, whose backward is traced outside _low). Off
    the chip they run in Pallas's interpreter, as the flash kernels do."""
    import importlib
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _gmm(lhs, rhs, group_sizes, transpose_rhs=False):
    """lhs (m, k) times the matrix of each row's group, rhs (G, k, n), or
    (G, n, k) transposed: (m, n) in lhs's dtype, tiled from this shape."""
    (m, k), (groups, *kn) = lhs.shape, rhs.shape
    n = kn[0] if transpose_rhs else kn[1]
    with _low(lhs.dtype):
        return _megablox_backend().gmm(
            lhs, rhs, group_sizes, lhs.dtype, _tiled(m, k, n, groups),
            transpose_rhs=transpose_rhs, interpret=_interpret())


@jax.custom_vjp
def _megablox(lhs, rhs, group_sizes):
    return _gmm(lhs, rhs, group_sizes)


def _megablox_fwd(lhs, rhs, group_sizes):
    return _megablox(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _megablox_bwd(res, grad):
    """jax's own rule (megablox/ops.py), traced under _low, each call
    tiled from its own shape: d lhs is the grouped product with each
    group's matrix transposed (it contracts the forward's columns and
    gives its contraction's), d rhs the grouped product of lhs^T and the
    cotangent, a (k, n) matrix a group, tiled as the forward."""
    lhs, rhs, group_sizes = res
    (m, k), (groups, _, n) = lhs.shape, rhs.shape
    d_lhs = _gmm(grad, rhs, group_sizes, transpose_rhs=True)
    with _low(lhs.dtype):
        d_rhs = _megablox_backend().tgmm(
            lhs.swapaxes(0, 1), grad, group_sizes, rhs.dtype,
            _tiled(m, k, n, groups), interpret=_interpret())
    return d_lhs, d_rhs, None


_megablox.defvjp(_megablox_fwd, _megablox_bwd)


def grouped_matmul(lhs, rhs, group_sizes):
    """lhs (R, k) rows sorted by group times rhs (G, k, n), row r by the
    matrix of its group: (R, n) in lhs's dtype, float32 accumulation.
    group_sizes (G,) int32 sum to R. jax's `megablox` Pallas kernels (`gmm`,
    and `tgmm` for the matrices' gradient), which won the race on the chip
    (benchmark/moe_race.py; PERF.md section 6), each call tiled from
    its own shape (`_gmm_tiling`)."""
    return _megablox(lhs, rhs, group_sizes)


def moe_route(x, router, k, score="sigmoid", scaling=1.0, norm_topk=True,
              bias=None):
    """Scores over every expert the router knows, in float32; the k largest
    a token. x (N, d), router (d, E). Returns (experts (N, k) int32, weights
    (N, k) float32): weights = scaling * s_sel / sum(s_sel) with norm_topk,
    else scaling * s_sel. `bias` (E,): the k largest of s + bias are
    chosen, and weighted by s alone (an expert bias balanced without a
    loss: `moe_balance`)."""
    if score not in SCORES:
        raise ValueError(f"router score {score!r}: one of {SCORES}")
    with jax.named_scope("moe_route"):
        logits = jnp.matmul(x.astype(jnp.float32), router.astype(jnp.float32),
                            precision=lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits) if score == "sigmoid" \
            else jax.nn.softmax(logits, axis=-1)
        if bias is None:
            top, experts = lax.top_k(s, k)
        else:
            _, experts = lax.top_k(s + bias.astype(jnp.float32), k)
            top = jnp.take_along_axis(s, experts, axis=-1)
        if norm_topk:
            top = top / jnp.sum(top, axis=-1, keepdims=True)
        return experts.astype(jnp.int32), top * scaling


def moe_dispatch(experts, held, rows, total=None):
    """Which token slots this chip computes, in the order its experts take
    them. experts (N, k) int32; held = (first, count): the experts
    [first, first + count) are here. Returns (slot (rows,) int32: indices
    into the N * k slots, sorted by local expert, spare rows last; group
    (count,) int32: rows a held expert takes, the spare ones with the last,
    summing to `rows`; live (rows,) bool: rows that are a held slot;
    counts {"held_slots", "slots_over"}). With `total`, the router's count
    of experts, the slots are counted for all of them and counts also holds
    that "load" (total,) int32; the held experts' sizes are its slice."""
    first, count = held
    with jax.named_scope("moe_dispatch"):
        flat = experts.reshape(-1)
        n = flat.shape[0]
        if rows > n or (count + 1) * n >= 2 ** 31:
            raise ValueError(f"{rows} rows, {count} held experts for {n} "
                             "token slots: more rows than slots, or a "
                             "sort key past int32")
        local = jnp.where((flat >= first) & (flat < first + count),
                          flat - first, count)         # sentinel: elsewhere
        # one sort of one array: the key in the high part, the slot in the low
        order = jnp.sort(local * n + jnp.arange(n, dtype=jnp.int32))[:rows]
        slot, key = order % n, order // n
        if total is None:
            sizes = jnp.sum(local[:, None] == jnp.arange(count)[None, :],
                            axis=0, dtype=jnp.int32)
        else:
            load = jnp.sum(flat[:, None] == jnp.arange(total)[None, :],
                           axis=0, dtype=jnp.int32)
            sizes = load[first:first + count]
        ends = jnp.minimum(jnp.cumsum(sizes), rows)
        group = jnp.diff(ends, prepend=0)
        group = group.at[count - 1].add(rows - ends[-1])
        held_slots = jnp.sum(sizes)
        live = key < count
        counts = {"held_slots": held_slots,
                  "slots_over": jnp.maximum(held_slots - rows, 0)}
        if total is not None:
            counts["load"] = load
        return slot, group, live, counts


def moe_routed(x, router, w_gate_in, w_out, *, held, k, rows,
               score="sigmoid", scaling=1.0, norm_topk=True, bias=None):
    """The routed part of one expert layer as this chip computes it:
    sum over a token's chosen AND held experts e of w_e SwiGLU_e(x).

    x (N, d); router (d, E) over ALL experts; w_gate_in (count, d, 2 f): a
    held expert's gate and up projections side by side; w_out (count, f, d);
    bias (E,) or None: moe_route's selection bias.
    Returns (y (N, d) in x's dtype, counts: moe_dispatch's, and "experts"
    (N, k), what each token chose, for whoever compares routings; with a
    bias also "load" (E,) int32, the token slots each of the E experts
    drew, which `moe_balance` reads)."""
    N, d = x.shape
    f = w_out.shape[1]
    if w_gate_in.shape != (held[1], d, 2 * f):
        raise ValueError(f"held {held}: gate and up projections "
                         f"{w_gate_in.shape}, down {w_out.shape}")
    experts, weights = moe_route(x, router, k, score, scaling, norm_topk,
                                 bias)
    slot, group, live, counts = moe_dispatch(
        experts, held, rows, None if bias is None else bias.shape[0])
    with jax.named_scope("moe_dispatch"):
        token = slot // k
        taken = x[token]                                    # (rows, d)
        w = jnp.where(live, weights.reshape(-1)[slot], 0.0)
    with jax.named_scope("moe_experts"):
        h = grouped_matmul(taken, w_gate_in, group)
        h = (jax.nn.silu(h[:, :f]) * h[:, f:]).astype(x.dtype)
        out = grouped_matmul(h, w_out, group)
    with jax.named_scope("moe_combine"):
        y = jnp.zeros((N, d), jnp.float32).at[token].add(
            out.astype(jnp.float32) * w[:, None])
        return y.astype(x.dtype), dict(counts, experts=experts)


def moe_balance(bias, load, rate):
    """The expert bias after one step of balancing without an auxiliary
    loss (DeepSeek-V3, arXiv:2412.19437 section 2.1.2): b + rate *
    sign(mean(c) - c), c (E,) the token slots each expert drew this step
    (moe_routed's "load"; their mean is N k / E). An expert drawn more than
    the mean is chosen less next step, one drawn less is chosen more; no
    gradient, no optimizer state."""
    load = load.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(load) - load)
