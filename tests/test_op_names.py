"""The program's names on its compiled operations: `jax.named_scope` in the
two train steps and in `apply_op`'s traced branch, a `name` on each flash
kernel. They act while a program is traced and reach the executable's
metadata (`op_name`), which is what a device trace is read by
(perfbench/op_scopes.py); nothing runs per step, and eager dispatch enters
no scope. Both executable caches must tell a named program from an unnamed
one."""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import compile_cache as cc
from incubator_mxnet_tpu import gluon


def _op_names(compiled_text):
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


@pytest.fixture(scope="module")
def trainstep_names():
    from incubator_mxnet_tpu.parallel import TrainStep
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(4, 3, padding=1), gluon.nn.BatchNorm(),
            gluon.nn.Activation("relu"), gluon.nn.GlobalAvgPool2D(),
            gluon.nn.Dense(3))
    net.initialize()

    def loss_fn(out, label):
        logp = jax.nn.log_softmax(out.astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(
            logp, label.astype(jnp.int32)[:, None], 1))

    step = TrainStep(net, loss_fn, optimizer="sgd",
                     optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                     example_inputs=[mx.nd.ones((2, 3, 8, 8))])
    x, y = jnp.ones((2, 3, 8, 8)), jnp.zeros((2,), jnp.int32)
    text = jax.jit(step._step_fn).lower(
        step.params, step.opt_state, jax.random.PRNGKey(0), 0, x,
        y).compile().as_text()
    return _op_names(text)


@pytest.fixture(scope="module")
def lm_names():
    from incubator_mxnet_tpu.models.transformer import (TransformerConfig,
                                                         TransformerLM)
    from incubator_mxnet_tpu.parallel import make_mesh
    model = TransformerLM(TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_len=16, remat=True, flash_attention=True))
    step, shard, init_opt = model.make_train_step(
        make_mesh({"dp": 1}, jax.devices()[:1]), use_sp=False)
    params = shard(model.init_params(jax.random.PRNGKey(0)))
    tokens = jnp.zeros((2, 16), jnp.int32)
    text = step.lower(params, init_opt(params), tokens, tokens,
                      0).compile().as_text()
    return _op_names(text)


@pytest.mark.parametrize("pattern", [
    r"^jit\(\w+\)/jvp\(forward\)/",
    r"^jit\(\w+\)/transpose\(jvp\(forward\)\)/",
    r"^jit\(\w+\)/(jvp\()?loss\)?/",
    r"^jit\(\w+\)/optimizer/",
    r"/jvp\(forward\)/BatchNorm/",
    r"/transpose\(jvp\(forward\)\)/BatchNorm/",
    r"/jvp\(forward\)/Convolution/",
    r"/jvp\(forward\)/FullyConnected/",
    r"/jvp\(forward\)/Pooling/",
])
def test_trainstep_operations_carry_the_programs_names(trainstep_names,
                                                       pattern):
    assert any(re.search(pattern, n) for n in trainstep_names), \
        sorted(trainstep_names)[:40]


@pytest.mark.parametrize("pattern", [
    r"^jit\(step\)/jvp\(forward\)/embed/",
    r"^jit\(step\)/jvp\(forward\)/layer1/attn/",
    r"^jit\(step\)/jvp\(forward\)/layer0/mlp/",
    r"^jit\(step\)/jvp\(forward\)/final_ln/",
    r"^jit\(step\)/jvp\(forward\)/logits/",
    r"^jit\(step\)/(jvp\()?loss\)?/",
    r"^jit\(step\)/optimizer/",
    r"^jit\(step\)/transpose\(jvp\(forward\)\)/layer1/.*/checkpoint/"
    r"rematted_computation/attn/",
    r"/jvp\(forward\)/layer0/attn/flash_fwd/",
    r"/rematted_computation/mlp/",
    r"/attn/flash_bwd_dq/",
    r"/transpose\(jvp\(forward\)\)/layer0/.*/checkpoint/attn/flash_bwd_dq/",
])
def test_lm_step_operations_carry_the_programs_names(lm_names, pattern):
    assert any(re.search(pattern, n) for n in lm_names), \
        sorted(lm_names)[:40]


def test_a_lm_steps_backward_is_one_kernel_a_layer(lm_names):
    """16 positions are one grid block in q and in k: the one backward call
    bears the dq kernel's name, and no dk/dv kernel stands beside it (PR
    37)."""
    assert not [n for n in lm_names if "flash_bwd_dkv" in n]
    for layer in ("layer0", "layer1"):
        assert any(f"/{layer}/checkpoint/attn/flash_bwd_dq/" in n
                   for n in lm_names), layer


def test_a_lm_step_runs_no_flash_forward_twice(lm_names):
    """The block's checkpoint keeps the kernel's output and lse (PR 35), so
    the recomputed forward holds the projections and no `flash_fwd`."""
    again = sorted(n for n in lm_names
                   if re.search(r"/rematted_computation/.*flash_fwd", n))
    assert again == []
    assert any(re.search(r"/rematted_computation/attn/", n)
               for n in lm_names)


@pytest.fixture(scope="module")
def laguna_names():
    """The step of a five-layer list with Laguna-S-2.1's pattern at tiny
    widths: "gqa" layers full and windowed, a dense MLP and four expert
    layers (the names perfbench/layer_metrics' moe_* and flash_win_* read)."""
    from incubator_mxnet_tpu.models.transformer import (
        GQA, Experts, Rotary, TransformerConfig, TransformerLM)
    from incubator_mxnet_tpu.parallel import make_mesh
    yarn = Rotary(500000.0, 0.5, (128, 8192, 32, 1, 1.4852))
    model = TransformerLM(TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=5, d_ff=64,
        max_len=64, remat=True, flash_attention=True, mixers=("gqa",) * 5,
        norm="rmsnorm", mlp="swiglu", learned_positions=False,
        tied_head=False, n_kv_heads=1, head_dim=16,
        gqa=(GQA(2, None, yarn),) + (GQA(3, 16),) * 3 + (GQA(2, None, yarn),),
        mlps=("dense",) + ("experts",) * 4,
        experts=Experts(count=8, held=(2, 4), per_token=2, width=16,
                        shared_width=16, scaling=2.5, rows=64)))
    step, shard, init_opt = model.make_train_step(
        make_mesh({"dp": 1}, jax.devices()[:1]), use_sp=False)
    params = shard(model.init_params(jax.random.PRNGKey(0)))
    tokens = jnp.zeros((1, 64), jnp.int32)
    text = step.lower(params, init_opt(params), tokens, tokens,
                      0).compile().as_text()
    return _op_names(text)


@pytest.mark.parametrize("pattern", [
    r"/jvp\(forward\)/layer1/mlp/moe/moe_route/",
    r"/jvp\(forward\)/layer2/mlp/moe/moe_dispatch/",
    r"/jvp\(forward\)/layer3/mlp/moe/moe_experts/",
    r"/jvp\(forward\)/layer4/mlp/moe/moe_shared/",
    r"/jvp\(forward\)/layer1/mlp/moe/moe_combine/",
    r"/transpose\(jvp\(forward\)\)/layer1/.*/mlp/moe/moe_experts/",
    r"/rematted_computation/mlp/moe/moe_dispatch/",
    r"/jvp\(forward\)/layer1/attn/window_attn/flash_win_fwd/",
    r"/attn/window_attn/flash_win_bwd_dq/",
    r"/transpose\(jvp\(forward\)\)/layer4/.*/checkpoint/attn/flash_bwd_dq/",
    r"/jvp\(forward\)/layer0/attn/flash_fwd/",
    r"/jvp\(forward\)/layer4/attn/flash_fwd/",
    r"/jvp\(forward\)/layer0/attn/rope/",
    r"/jvp\(forward\)/layer2/attn/rope/",
    r"/jvp\(forward\)/layer2/attn/gate/",
    r"/jvp\(forward\)/layer0/mlp/norm/",
    r"^jit\(step\)/optimizer/",
])
def test_a_layer_list_with_experts_carries_the_programs_names(laguna_names,
                                                              pattern):
    assert any(re.search(pattern, n) for n in laguna_names), \
        sorted(laguna_names)[:40]


def test_a_windowed_layers_backward_is_one_kernel(laguna_names):
    """A band's backward is one call under the windowed dq kernel's name, as
    a full layer's is under the plain one's: no dk/dv kernel beside either
    (PR 39)."""
    assert not [n for n in laguna_names if "bwd_dkv" in n]
    for layer in ("layer1", "layer2", "layer3"):
        assert any(re.search(
            rf"/{layer}/.*attn/window_attn/flash_win_bwd_dq/", n)
            for n in laguna_names), layer


def test_a_windowed_layer_runs_no_flash_forward_twice(laguna_names):
    """A block's checkpoint keeps a windowed call's output and lse as it
    keeps a plain one's; the full layers' kernels stay outside
    `window_attn`, where the plain readers count them."""
    assert not [n for n in laguna_names if re.search(
        r"/rematted_computation/.*flash_(win_)?fwd", n)]
    assert not [n for n in laguna_names
                if re.search(r"window_attn/flash_(fwd|bwd)", n)]
    assert not [n for n in laguna_names
                if re.search(r"layer[04]/.*window_attn", n)]


@pytest.mark.parametrize("which", ["trainstep_names", "lm_names",
                                   "laguna_names"])
def test_a_step_leaves_no_operation_unnamed(which, request):
    """Every operation a step traces (`jit(..)/..`; the rest are labels of
    arguments) sits under one of the three top words: what a later edit
    leaves outside them shows up here before `unnamed_time_share` shows it
    on the chip."""
    names = request.getfixturevalue(which)
    tops = re.compile(r"[/(](forward|loss|optimizer)[/)]")
    bare = sorted(n for n in names
                  if n.startswith("jit(") and not tops.search(n))
    assert bare == []


@pytest.mark.parametrize("cell,family,layer,tiles", [
    ("laguna-s-2.1.train-8k", "laguna", 1, {(512, 1024, 1024): 8}),
    ("lfm2-8b-a1b.train-4x8k-moe", "lfm2_moe", 2,
     {(512, 1024, 896): 4, (512, 896, 1024): 4})], ids=["laguna", "lfm2"])
def test_an_expert_layers_grouped_products_take_their_own_tiles(
        cell, family, layer, tiles):
    """Value and gradient of one remat block with experts of the cell at its
    published widths and batch, traced and not run: the eight megablox
    calls (each product's forward twice, the rows' and the matrices'
    gradients) and their tiles, by `grouped_stats()`. Laguna's widths are
    multiples of 1,024, so every call keeps the 1,024 x 1,024 it had; LFM2's
    1,792 and 3,584 go in 896-wide tiles. Both buffers hold 1,024 rows or
    more a held expert (1,536 and 9,216), so 512 rows a tile."""
    import importlib
    from perfbench import cells
    from incubator_mxnet_tpu.models.transformer import (TransformerLM,
                                                        _remat_policy)
    from incubator_mxnet_tpu.parallel import moe
    cell = cells.resolve(cell)
    fam = importlib.import_module(f"perfbench.families.{family}")
    model = TransformerLM(fam.model_config(cell.config, cell.traffic))
    prefix = f"layer{layer}_"
    params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in
              jax.eval_shape(model.init_params, jax.random.PRNGKey(0)).items()
              if k.startswith(prefix)}
    x = jax.ShapeDtypeStruct((cell.traffic["batch_per_chip"],
                              cell.traffic["seq_len"], model.cfg.d_model),
                             jnp.bfloat16)
    block = jax.checkpoint(lambda p, y: model._block(p, prefix, y, None),
                           policy=_remat_policy(None))
    loss = lambda p, y: block(p, y)[0].astype(jnp.float32).sum()
    before = moe.grouped_stats()["tiles"]
    jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1)))(params, x)
    after = moe.grouped_stats()["tiles"]
    assert {t: c - before.get(t, 0) for t, c in after.items()
            if c > before.get(t, 0)} == tiles


@pytest.mark.parametrize("traced", [False, True])
def test_apply_op_enters_a_scope_only_inside_a_trace(monkeypatch, traced):
    """The eager branch is untouched: no scope, no cost per call."""
    entered = []
    real = jax.named_scope

    def spy(name):
        entered.append(name)
        return real(name)
    monkeypatch.setattr(jax, "named_scope", spy)
    x = np.ones((2, 3), np.float32)
    if traced:
        out = jax.jit(lambda a: mx.nd.relu(mx.nd.NDArray(a))._data)(x)
    else:
        out = mx.nd.relu(mx.nd.array(x))._data
    assert np.asarray(out).shape == (2, 3)
    assert entered == (["relu"] if traced else [])


def _plain(x):
    return jnp.sin(x) * 2


def _scoped(x):
    with jax.named_scope("forward"):
        return jnp.sin(x) * 2


def test_the_executable_cache_tells_a_named_program_from_an_unnamed_one():
    """`compile_cache` fingerprints the jaxpr's text, which prints no name
    stack unless asked: a cached executable without names must not be
    served to a program that has them."""
    args = (jnp.ones((4,)),)
    fp_plain, _ = cc.cached_jit("names:fp", _plain)._fingerprint_for(args, {})
    fp_scoped, _ = cc.cached_jit("names:fp", _scoped)._fingerprint_for(
        args, {})
    again, _ = cc.cached_jit("names:fp", _scoped)._fingerprint_for(args, {})
    assert fp_plain != fp_scoped and fp_scoped == again


def test_jaxs_persistent_cache_keys_on_metadata():
    """jax strips metadata (names among it) from its cache key unless
    `jax_compilation_cache_include_metadata_in_key` is set; the package
    sets it at import. The computation's share of the key then differs."""
    from jax._src import cache_key
    assert jax.config.jax_compilation_cache_include_metadata_in_key

    def digest(fn):
        h = hashlib.sha256()
        cache_key._hash_computation(
            h, jax.jit(fn).lower(jnp.ones((4,))).compiler_ir(),
            cache_key.IgnoreCallbacks.NO)
        return h.hexdigest()
    assert digest(_plain) != digest(_scoped)
    assert digest(_scoped) == digest(_scoped)


_STALE = '''
import contextlib, os, sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
if sys.argv[2] == "package":
    import incubator_mxnet_tpu          # sets the cache's options
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
def f(x):
    scope = jax.named_scope("forward") if sys.argv[1] == "named" \\
        else contextlib.nullcontext()
    with scope: return jnp.sin(x) * 2       # one line: one source location
text = jax.jit(f).lower(jnp.ones((64,))).compile().as_text()
print("HAS_NAME", "forward" in text, sum(n.startswith("jit_f-") for n in
      os.listdir(os.environ["JAX_COMPILATION_CACHE_DIR"])))
'''


@pytest.mark.parametrize("how,stale", [("package", False), ("bare", True)])
def test_a_cache_filled_without_names_then_a_program_with_them(
        tmp_path, how, stale):
    """The hazard end to end, in two processes sharing jax's persistent
    cache: the first compiles the program without a scope, the second the
    same program with one. Bare jax serves the second the first's
    executable, which names nothing; under the package's setting it
    compiles again and the name is there."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "stale.py"
    script.write_text(_STALE.format(root=root))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    said = []
    for names in ("plain", "named"):
        out = subprocess.run(
            [sys.executable, str(script), names, how], env=env, text=True,
            capture_output=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        said.append(out.stdout.strip().splitlines()[-1].split()[1:])
    assert said[0] == ["False", "1"]
    assert said[1] == (["False", "1"] if stale else ["True", "2"])


# -- the kernels' names in a program compiled for the chip --------------------
# The TPU's compiler is installed here and compiles for a chip that is
# described, not attached. The topology is described inside a fixture, never
# at import, and only this file of the suite does it (one process at a time
# may hold the TPU's library).

@pytest.fixture(scope="module")
def v5e():
    import os
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — whatever libtpu raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(v5e):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e.devices[0])


_TILED = re.compile(r"\b(f32)\[([\d,]*)\]\{[\d,]*:T\((\d+),(\d+)\)")


def _padded(line):
    """The f32 arrays named on a compiled instruction's line whose tiled
    layout holds more than twice their shape's numbers: [(shape, times)]."""
    found = []
    for m in _TILED.finditer(line):
        dims = [int(x) for x in m.group(2).split(",") if x]
        if len(dims) < 2:
            continue
        a, b = int(m.group(3)), int(m.group(4))
        times = (-(-dims[-2] // a) * a * -(-dims[-1] // b) * b) \
            / (dims[-2] * dims[-1])
        if times > 2:
            found.append((m.group(0), times))
    return found


def test_the_padding_reader_tells_a_column_from_a_row():
    assert _padded("f32[512,1024,1]{2,1,0:T(8,128)} custom-call(") \
        == [("f32[512,1024,1]{2,1,0:T(8,128)", 128.0)]
    assert not _padded("(bf16[32,1024,1024]{2,1,0:T(8,128)(2,1)}, "
                       "f32[512,1,1024]{2,1,0:T(1,128)}) custom-call(")


@pytest.mark.parametrize("kernels, dlse, t, window", [
    (("flash_fwd",), False, 1024, None),
    (("flash_bwd_dq",), False, 1024, None),
    (("flash_bwd_dq",), True, 1024, None),
    (("flash_bwd_dq",), False, 2048, None),
    (("flash_win_bwd_dq",), False, 2048, 512)],
    ids=["flash_fwd", "flash_bwd", "flash_bwd_hop", "flash_bwd_pair",
         "flash_win_bwd"])
def test_flash_kernels_keep_their_names_in_a_tpu_program(one_chip, kernels,
                                                         dlse, t, window):
    """GPT-2 medium's shapes, (B, T, H * D) = (32, 1024, 16 * 64) in 1024 x
    1024 blocks of two heads: each kernel is ONE custom call whose
    instruction and `op_name` carry the kernel's name under the caller's
    scopes. `flash_time_share` reads the opcode, `flash_*_roofline` the name
    and the operand shapes: q, k, v first, three dimensions each, from which
    the benchmark counts what it counted from (B * H, T, D) = (512, 1024,
    64). The row vectors are (B * H, 1, T), rows of lanes that no tile pads.
    The backward is ONE call, named `flash_bwd_dq` with the dq kernel's
    operands in its order (six, a ring hop's cotangent of lse a seventh) and
    dq, dk, dv for results; delta is no array of the program: at T = 1,024,
    where a call is one grid block in q and in k (PR 37), and at T = 2,048
    in the same blocks, where the pair stood until PR 39 (the case keeps its
    name), plain or, as `flash_win_bwd_dq`, with a window."""
    import importlib
    from perfbench import op_scopes
    fa = importlib.import_module(     # the package exports a function by
        "incubator_mxnet_tpu.parallel.flash_attention")     # the same name
    big = jax.ShapeDtypeStruct((32, t, 1024), jnp.bfloat16,
                               sharding=one_chip)
    vec = jax.ShapeDtypeStruct((512, 1, t), jnp.float32,
                               sharding=one_chip)

    def fwd(q, k, v):
        with jax.named_scope("forward"), jax.named_scope("attn"):
            return fa._fa_forward(q, k, v, 64, True, 0.125, 1024, 1024,
                                  False)

    def bwd(q, k, v, do, lse, out, dlse=None):
        with jax.named_scope("forward"), jax.named_scope("attn"):
            return fa._fa_backward(q, k, v, do, lse, out, dlse, 64, True,
                                   0.125, 1024, 1024, False, window)
    if kernels == ("flash_fwd",):
        lowered = jax.jit(fwd).lower(big, big, big)
    else:
        lowered = jax.jit(bwd).lower(big, big, big, big, vec, big,
                                     *[vec] * dlse)
    text = lowered.compile().as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == len(kernels)
    for name, line in zip(kernels, calls):
        assert re.match(rf"\s*(ROOT )?%{name}(\.\d+)? = ", line), line[:200]
        assert re.search(rf'op_name="jit\(\w+\)/forward/attn/{name}/'
                         r'pallas_call"', line), line[-400:]
        assert f"bf16[32,{t},1024]" in line.split("custom-call(")[1]
        assert not _padded(line), _padded(line)
    # the benchmark's count of the first call, from the compiled call's own
    # operand and result shapes, as `op_scopes` reads them off a trace
    shapes = lambda text: [f"{m.group(1)}[{m.group(2)}]"
                           for m in op_scopes.SHAPE.finditer(text)]
    row = {"results": shapes(calls[0].split(" custom-call(")[0]),
           "operands": shapes(calls[0].split(
               "operand_layout_constraints={")[1].split("}}")[0])}
    backward = kernels[0] != "flash_fwd"
    assert row["operands"][:3] == [f"bf16[32,{t},1024]"] * 3
    assert op_scopes.flash_dims(row) == (32, t, 1024, 1024)
    if t != 1024:
        # the one call of a several-block grid: the six operands in the dq
        # kernel's order, dq, dk, dv for results, no delta among them
        assert row["operands"] == [f"bf16[32,{t},1024]"] * 4 \
            + [f"f32[512,1,{t}]", f"bf16[32,{t},1024]"]
        assert row["results"] == [f"bf16[32,{t},1024]"] * 3
        return
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = op_scopes.flash_least_seconds(row, peaks, backward)
    old = (512, 1024, 64)
    flops = op_scopes.flash_flops(*op_scopes.flash_dims(row)[:3],
                                  backward=backward)
    assert flops == op_scopes.flash_flops(*old, backward=backward) \
        == (2 if backward else 1) * 68_719_476_736
    moved = sum(map(op_scopes.shape_bytes, row["operands"] + row["results"]))
    if not backward:
        # q, k, v, o at 64 MiB and lse, 2 MiB by its shape: 270 MB
        assert len(row["operands"]) == 3 and moved == (4 * 64 + 2) * 2**20
        assert (least, bound) == (flops / 197e12, "FLOPs")
        return
    # q, k, v, dO, O, and lse and a hop's dlse; dq, dk, dv: every array of a
    # layer's backward once, 539 MB, under the 697.7 us of its FLOPs
    assert len(row["operands"]) == 6 + dlse and len(row["results"]) == 3
    assert row["results"] == ["bf16[32,1024,1024]"] * 3
    assert moved == (8 * 64 + (1 + dlse) * 2) * 2**20
    assert moved / 819e9 < flops / 197e12
    # the benchmark's reader adds k's and v's bytes to a `flash_bwd_dq` row
    # for the dk and dv of a dkv call beside it; this call lists them among
    # its own results, so they are counted twice and `flash_bwd_roofline`
    # over-reads by 1.18 at this shape: a `benchmark` PR's to correct
    # (PERF.md section 3, ROADMAP S1)
    assert bound == "bytes"
    assert least == (moved + 2 * 64 * 2**20) / 819e9
    assert 1.17 < least / (flops / 197e12) < 1.19


_QKV_SHAPED = ("bf16[32,1024,16,64]", "bf16[32,16,1024,64]",
               "bf16[512,1024,64]", "bf16[512,64,1024]")


def test_no_copy_stands_round_a_flash_call_in_a_tpu_program(one_chip,
                                                            monkeypatch):
    """Value and gradient of one remat `TransformerLM` block at GPT-2
    medium's widths, batch 32 x 1,024, compiled for the chip: the forward
    kernel and the one backward call by name, each once (the block's
    checkpoint keeps the forward's output and lse, so it is not run again;
    1,024 positions are one grid block, so no dk/dv call), no `transpose` or
    `copy`, alone or as a fusion, over an array shaped like q, k, v or O in
    either of the layouts the kernels took before PR 33, and no f32 operand
    or result of a kernel that its tiles pad to over twice its numbers."""
    from incubator_mxnet_tpu.models.transformer import (TransformerConfig,
                                                        TransformerLM,
                                                        _remat_policy)
    # the kernels ask the backend whether to interpret: compile them
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = TransformerLM(TransformerConfig(
        vocab_size=50257, d_model=1024, n_heads=16, n_layers=1, d_ff=4096,
        max_len=1024, remat=True, flash_attention=True))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)
              for k, v in shapes.items() if k.startswith("layer0_")}
    x = jax.ShapeDtypeStruct((32, 1024, 1024), jnp.bfloat16,
                             sharding=one_chip)
    block = jax.checkpoint(
        lambda p, y: model._block(p, "layer0_", y, None),
        policy=_remat_policy(None))

    def loss(p, y):
        return block(p, y).astype(jnp.float32).sum()
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    calls, moves, padded = [], [], []
    for ln in text.splitlines():
        # %name = type opcode(..: the type may be a tuple with spaces in it
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\(",
                     ln)
        if not m:
            continue
        name, shape, opcode = m.groups()
        if 'custom_call_target="tpu_custom_call"' in ln:
            calls.append(name.split(".")[0])
            padded += _padded(ln)
        moving = opcode in ("transpose", "copy") or (
            opcode == "fusion" and re.search("transpose|copy", name))
        if moving and shape.startswith(_QKV_SHAPED):
            moves.append(ln.strip()[:200])
    assert sorted(calls) == ["flash_bwd_dq", "flash_fwd"], calls
    assert not moves, moves
    assert not padded, padded


def test_keeping_the_flash_residuals_costs_no_memory_in_a_tpu_program(
        one_chip, monkeypatch):
    """Value and gradient of `TransformerLM.loss` at GPT-2 medium's sizes,
    24 layers at 32 x 1,024, compiled for the chip twice: as the model
    builds its blocks' checkpoints, and with checkpoints that keep nothing.
    Kept: each of a layer's two kernel calls once (the unkept program runs
    the forward twice; the backward is one call, PR 37), no row vector that
    its tiles pad, no array of zeros in
    the cotangent's place, no copy of an activation that the unkept program
    does not make (the output kept as (B, T, H, D) was copied twice a layer:
    to the chip's tiles that is another array than the kernel's (N, T, C)),
    and temporaries no more than the unkept program's by the kept arrays
    themselves, a layer's O (B, T, H * D) bf16 and lse (B * H, 1, T) f32
    (lse as a column of 268 MB a layer made this +4.1 GB). Until the loss
    stopped writing an f32 (B, T, V) log-softmax, that array set the peak of
    both programs and hid the kept arrays altogether."""
    from incubator_mxnet_tpu.models import transformer
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = transformer.TransformerLM(transformer.TransformerConfig(
        vocab_size=50257, d_model=1024, n_heads=16, n_layers=24, d_ff=4096,
        max_len=1024, remat=True, flash_attention=True))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)
              for k, v in shapes.items()}
    tokens = jax.ShapeDtypeStruct((32, 1024), jnp.int32, sharding=one_chip)

    def compiled():
        done = jax.jit(jax.value_and_grad(model.loss)).lower(
            params, tokens, tokens).compile()
        text = done.as_text()
        calls = [ln for ln in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in ln]
        names = [re.match(r"\s*(?:ROOT )?%(\w+?)(\.\d+)? = ", ln).group(1)
                 for ln in calls]
        return ({n: names.count(n) for n in set(names)}, calls,
                done.memory_analysis().temp_size_in_bytes, text)
    copies = lambda text: len(re.findall(
        r"= \w+\[32,1024,(1024|16,64)\]\S* copy\(", text))
    counts, calls, kept, text = compiled()
    assert counts == {"flash_fwd": 24, "flash_bwd_dq": 24}
    assert not [p for ln in calls for p in _padded(ln)]
    assert not re.search(r"= f32\[512,1,1024\]\S* broadcast\(", text)
    monkeypatch.setattr(transformer, "_remat_policy", lambda name: None)
    counts, _, unkept, unkept_text = compiled()
    assert counts["flash_fwd"] == 48
    assert copies(text) <= copies(unkept_text)
    held = 24 * (32 * 1024 * 1024 * 2 + 512 * 1024 * 4)
    assert kept - unkept <= held, (kept, unkept, held)


def _materialised(text):
    """The instruction lines of a compiled program that write an array of
    their own: those outside the computations that fusions call."""
    fused = set(re.findall(r" fusion\(.*?calls=%([\w.\-]+)", text))
    inside, lines = None, []
    for ln in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) ", ln)
        if head:
            inside = head.group(1)
        elif inside not in fused and re.match(r"\s*(ROOT )?%", ln):
            lines.append(ln)
    return lines


def test_the_loss_writes_no_f32_vocabulary_wide_array_in_a_tpu_program(
        one_chip):
    """Value and gradient of `TransformerLM.loss`, 2 layers at 4 x 256
    tokens and a vocabulary of 8,192, compiled for the chip beside the
    log-softmax form of the same logits: no operation of the loss's step
    writes an f32 (B, T, V) array, which the log-softmax form writes whole
    to gather one number a token from, and the step accesses at least that
    array's B * T * V * 4 bytes fewer."""
    from incubator_mxnet_tpu.models.transformer import (TransformerConfig,
                                                        TransformerLM)
    B, T, V = 4, 256, 8192
    model = TransformerLM(TransformerConfig(
        vocab_size=V, d_model=256, n_heads=2, n_layers=2, d_ff=1024,
        max_len=T, remat=True, flash_attention=False))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)
              for k, v in shapes.items()}
    tokens = jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=one_chip)

    def log_softmax_form(p, x, y):
        logp = jax.nn.log_softmax(model.apply(p, x), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y[..., None],
                                             axis=-1)[..., 0])

    def compiled(loss):
        done = jax.jit(jax.value_and_grad(loss)).lower(
            params, tokens, tokens).compile()
        return done.as_text(), done.cost_analysis()
    wide = lambda text: [ln.strip()[:200] for ln in _materialised(text)
                         if f"= f32[{B},{T},{V}]" in ln]
    text, cost = compiled(model.loss)
    old_text, old_cost = compiled(log_softmax_form)
    assert wide(old_text)
    assert not wide(text), wide(text)
    assert cost["bytes accessed"] <= \
        old_cost["bytes accessed"] - B * T * V * 4, \
        (cost["bytes accessed"], old_cost["bytes accessed"])
    # the same products: the two forms differ by a few operations a token
    assert cost["flops"] == pytest.approx(old_cost["flops"], rel=1e-5)


@pytest.mark.parametrize("layer", [0, 1], ids=["sparse", "lightning"])
def test_a_layer_lists_blocks_keep_their_names_in_a_tpu_program(
        one_chip, monkeypatch, layer):
    """Value and gradient of one remat block of `minicpm-sala.train-8k`, at
    its published widths and 1 x 8,192 tokens, compiled for the chip. The
    "sparse" layer on its dense path is `flash_fwd` and the one backward
    call (`flash_bwd_dq`: dq, dk, dv; PR 39) over K/V repeated to the 32
    query heads, so that the benchmark's count reads
    (1, 8192, 32 * 128) off every operand; the "lightning" layer is plain
    XLA under the scopes its metrics read, with no kernel and no (T, T)
    array."""
    from perfbench import cells, op_scopes
    from perfbench.families import minicpm_sala
    from incubator_mxnet_tpu.models.transformer import (TransformerLM,
                                                        _remat_policy)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = cells.resolve("minicpm-sala.train-8k")
    model = TransformerLM(minicpm_sala.model_config(cell.config,
                                                    cell.traffic))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    prefix = f"layer{layer}_"
    params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)
              for k, v in shapes.items() if k.startswith(prefix)}
    x = jax.ShapeDtypeStruct((1, 8192, 4096), jnp.bfloat16,
                             sharding=one_chip)
    block = jax.checkpoint(lambda p, y: model._block(p, prefix, y, None),
                           policy=_remat_policy(None))

    def loss(p, y):
        with jax.named_scope("forward"):
            return block(p, y).astype(jnp.float32).sum()
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    names = set(re.findall(r'op_name="([^"]*)"', text))
    under = lambda *words: any(all(f"/{w}/" in n for w in words)
                               for n in names)
    assert under("attn", "norm") and under("attn", "gate") \
        and under("mlp", "norm")
    if layer == 1:
        assert not calls
        assert under("attn", "lightning", "lightning_intra")
        assert under("attn", "lightning", "lightning_state")
        assert under("attn", "rope")
        assert under("rematted_computation", "lightning")
        assert not re.search(r"\[(\d+,)*8192,8192\]", text)
        return
    kernel = lambda ln: re.match(r"\s*(?:ROOT )?%(\w+?)(\.\d+)? = ",
                                 ln).group(1)
    # the sparse layer's backward is ONE call, dq, dk and dv its results
    assert sorted(map(kernel, calls)) == ["flash_bwd_dq", "flash_fwd"]
    assert not [p for ln in calls for p in _padded(ln)]
    assert under("attn", "sparse_attn", "flash_fwd")
    assert not under("block_select")            # T = dense_len: dense
    shaped = lambda text: [f"{m.group(1)}[{m.group(2)}]"
                           for m in op_scopes.SHAPE.finditer(text)]
    for ln in calls:
        row = {"results": shaped(ln.split(" custom-call(")[0]),
               "operands": shaped(ln.split(
                   "operand_layout_constraints={")[1].split("}}")[0])}
        assert row["operands"][:3] == ["bf16[1,8192,4096]"] * 3
        assert op_scopes.flash_dims(row) == (1, 8192, 4096, 4096)
        if kernel(ln) == "flash_bwd_dq":
            assert row["results"] == ["bf16[1,8192,4096]"] * 3
            # bound by its FLOPs, dk and dv counted twice or not
            assert op_scopes.flash_least_seconds(
                row, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
                True) == (2 * 549_755_813_888 / 197e12, "FLOPs")
    # 32 heads x 8192^2 x (128 + 128) under the mask, as (B H, T, D) counts
    assert op_scopes.flash_flops(1, 8192, 4096) == \
        op_scopes.flash_flops(32, 8192, 128) == 549_755_813_888


def test_a_windowed_expert_layer_keeps_its_names_in_a_tpu_program(
        one_chip, monkeypatch):
    """Value and gradient of one remat block of `laguna-s-2.1.train-8k`
    (layer 1: 72 heads inside a window of 512, routed and shared experts),
    at its published widths and 1 x 8,192 tokens, compiled for the chip:
    the windowed forward kernel and the one backward call by their own
    names, each once, over K/V repeated to the 72 query heads, so that
    `flash_win_*_roofline` reads (1, 8192, 72 * 128) off every operand and
    counts the band alone; the
    grouped products under `moe_experts`; no kernel named `flash_fwd`, which
    the plain readers would count as a whole causal square."""
    import importlib.util
    import os
    from perfbench import cells, op_scopes
    from perfbench.families import laguna
    from incubator_mxnet_tpu.models.transformer import (TransformerLM,
                                                        _remat_policy)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = cells.resolve("laguna-s-2.1.train-8k")
    model = TransformerLM(laguna.model_config(cell.config, cell.traffic))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)
              for k, v in shapes.items() if k.startswith("layer1_")}
    x = jax.ShapeDtypeStruct((1, 8192, 3072), jnp.bfloat16,
                             sharding=one_chip)
    block = jax.checkpoint(lambda p, y: model._block(p, "layer1_", y, None),
                           policy=_remat_policy(None))

    def loss(p, y):
        with jax.named_scope("forward"):
            return block(p, y)[0].astype(jnp.float32).sum()
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    kernel = lambda ln: re.match(r"\s*(?:ROOT )?%(\w+?)(\.\d+)? = ",
                                 ln).group(1)
    flash = [ln for ln in calls if kernel(ln).startswith("flash")]
    assert sorted(map(kernel, flash)) == ["flash_win_bwd_dq",
                                          "flash_win_fwd"]
    assert not [p for ln in flash for p in _padded(ln)]
    names = set(re.findall(r'op_name="([^"]*)"', text))
    under = lambda *words: any(all(f"/{w}/" in n for w in words)
                               for n in names)
    assert under("attn", "window_attn", "flash_win_fwd")
    assert under("attn", "rope") and under("attn", "gate")
    for scope in ("moe_route", "moe_dispatch", "moe_experts", "moe_shared",
                  "moe_combine"):
        assert under("mlp", "moe", scope), scope
    assert under("rematted_computation", "moe_experts")
    assert not under("rematted_computation", "flash_win_fwd")
    # every other kernel of the block is a grouped product of the experts
    rest = [ln for ln in calls if ln not in flash]
    assert rest and all("/moe_experts/" in re.search(
        r'op_name="([^"]*)"', ln).group(1) for ln in rest)
    shaped = lambda text: [f"{m.group(1)}[{m.group(2)}]"
                           for m in op_scopes.SHAPE.finditer(text)]
    spec = importlib.util.spec_from_file_location("win", os.path.join(
        cells.HERE, "layer_metrics", "flash_win_fwd_roofline.py"))
    win = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(win)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    for ln in flash:
        row = {"results": shaped(ln.split(" custom-call(")[0]),
               "operands": shaped(ln.split(
                   "operand_layout_constraints={")[1].split("}}")[0])}
        assert row["operands"][:3] == ["bf16[1,8192,9216]"] * 3
        assert op_scopes.flash_dims(row) == (1, 8192, 9216, 9216)
        backward = kernel(ln) == "flash_win_bwd_dq"
        least, bound = win.least_seconds(row, 512, peaks, backward)
        # 72 heads x (512 x 8192 - 512 x 511 / 2) pairs x 2 (128 + 128)
        flops = (2 if backward else 1) * 149_796_421_632
        if not backward:
            assert (least, bound) == (flops / 197e12, "FLOPs")
            continue
        # the one call lists dk and dv among its own results, and the reader
        # adds k's and v's bytes for them besides: eight arrays and lse
        # counted as ten, 1,846 us "of bytes" over the 1,521 us of its FLOPs.
        # `flash_win_bwd_roofline` over-reads by 1.21: a `benchmark` PR's to
        # correct (PERF.md section 3, ROADMAP S1f)
        assert row["results"] == ["bf16[1,8192,9216]"] * 3
        moved = sum(map(op_scopes.shape_bytes,
                        row["operands"] + row["results"]))
        assert moved / 819e9 < flops / 197e12
        assert bound == "bytes"
        assert 1.20 < least / (flops / 197e12) < 1.23


@pytest.mark.parametrize("layer", [0, 2], ids=["conv", "attention"])
def test_an_lfm2_block_keeps_its_names_in_a_tpu_program(one_chip,
                                                       monkeypatch, layer):
    """Value and gradient of one remat block of `lfm2-8b-a1b.train-4x8k-moe`
    at its published widths and 4 x 8,192 tokens, compiled for the chip.
    Layer 0 is a gated short convolution (and the dense SwiGLU): plain XLA
    under `short_conv` and `short_conv_taps`, recomputed by the remat, no
    kernel. Layer 2 is QK-normed, ungated GQA over experts: `flash_fwd` and
    the one backward call over K/V repeated to the 32 query heads, so that
    the benchmark's count reads (4, 8192, 32 * 64) off every operand, every
    other kernel a grouped product of the experts. Neither holds a (T, T)
    array."""
    from perfbench import cells, op_scopes
    from perfbench.families import lfm2_moe
    from incubator_mxnet_tpu.models.transformer import (TransformerLM,
                                                        _remat_policy)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = cells.resolve("lfm2-8b-a1b.train-4x8k-moe")
    model = TransformerLM(lfm2_moe.model_config(cell.config, cell.traffic))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    prefix = f"layer{layer}_"
    params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)
              for k, v in shapes.items() if k.startswith(prefix)}
    x = jax.ShapeDtypeStruct((4, 8192, 2048), jnp.bfloat16,
                             sharding=one_chip)
    block = jax.checkpoint(lambda p, y: model._block(p, prefix, y, None),
                           policy=_remat_policy(None))

    def loss(p, y):
        with jax.named_scope("forward"):
            out = block(p, y)
            out = out[0] if layer else out
            return out.astype(jnp.float32).sum()
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    names = set(re.findall(r'op_name="([^"]*)"', text))
    under = lambda *words: any(all(f"/{w}/" in n for w in words)
                               for n in names)
    assert not re.search(r"\[(\d+,)*8192,8192\]", text)
    assert under("attn", "norm") and under("mlp", "norm")
    if layer == 0:
        assert not calls
        assert under("attn", "short_conv", "short_conv_taps")
        assert under("rematted_computation", "short_conv")
        assert not under("attn", "rope") and not under("moe")
        return
    assert not under("short_conv") and not under("attn", "gate")
    assert under("attn", "rope")
    for scope in ("moe_route", "moe_dispatch", "moe_experts", "moe_combine"):
        assert under("mlp", "moe", scope), scope
    kernel = lambda ln: re.match(r"\s*(?:ROOT )?%(\w+?)(\.\d+)? = ",
                                 ln).group(1)
    flash = [ln for ln in calls if kernel(ln).startswith("flash")]
    assert sorted(map(kernel, flash)) == ["flash_bwd_dq", "flash_fwd"]
    rest = [ln for ln in calls if ln not in flash]
    assert rest and all("/moe_experts/" in re.search(
        r'op_name="([^"]*)"', ln).group(1) for ln in rest)
    shaped = lambda text: [f"{m.group(1)}[{m.group(2)}]"
                           for m in op_scopes.SHAPE.finditer(text)]
    for ln in flash:
        row = {"results": shaped(ln.split(" custom-call(")[0]),
               "operands": shaped(ln.split(
                   "operand_layout_constraints={")[1].split("}}")[0])}
        assert row["operands"][:3] == ["bf16[4,8192,2048]"] * 3
        assert op_scopes.flash_dims(row) == (4, 8192, 2048, 2048)
    # 32 heads x 4 x 8192^2 x (64 + 64) under the mask
    assert op_scopes.flash_flops(4, 8192, 2048) == \
        op_scopes.flash_flops(128, 8192, 64) == 1_099_511_627_776


# -- BatchNorm's all-reduces on a dp mesh -------------------------------------
# Under GSPMD a BatchNorm over a batch sharded on `dp` takes the statistics of
# the global batch, and every reduction over the batch becomes an all-reduce.
# What a layer needs: forward the shift's f32[C] and (s1, s2) as one, backward
# the two reductions of its gradient as one. The shift's own cotangent, an
# exact zero, used to add an f32[C] and a (1, C, H, W) map to each backward
# layer (`_bn_batch_stats`).

_BN_C = 8
_BN_LAYERS = {"BatchNorm": 1, "FusedBNAddReLU": 2, "FusedConvBNReLU": 1}


class _ThreeOps(gluon.HybridBlock):
    """conv, BatchNorm, conv, FusedBNAddReLU, conv, FusedBNAddReLU with a
    residual, FusedConvBNReLU: each registered op that takes batch
    statistics, in training mode."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.convs = gluon.nn.HybridSequential(prefix="")
            for _ in range(3):
                self.convs.add(gluon.nn.Conv2D(_BN_C, 3, padding=1,
                                               use_bias=False))
            self.weight = self.params.get("weight",
                                          shape=(_BN_C, _BN_C, 3, 3))
            for i in range(4):
                for name, init, diff in (("gamma", "ones", True),
                                         ("beta", "zeros", True),
                                         ("mean", "zeros", False),
                                         ("var", "ones", False)):
                    setattr(self, f"{name}{i}", self.params.get(
                        f"{name}{i}", shape=(_BN_C,), init=init,
                        differentiable=diff))

    def hybrid_forward(self, F, x, weight, **p):
        def bn(i):
            return [p[f"{n}{i}"] for n in ("gamma", "beta", "mean", "var")]
        kw = {"fix_gamma": False, "training": mx.autograd.is_training()}
        y = F.BatchNorm(self.convs[0](x), *bn(0), **kw)[0]
        z = F.FusedBNAddReLU(self.convs[1](y), *bn(1), **kw)[0]
        z = F.FusedBNAddReLU(self.convs[2](z), *bn(2), y, **kw)[0]
        return F.FusedConvBNReLU(z, weight, *bn(3), kernel=(3, 3),
                                 pad=(1, 1), num_filter=_BN_C, **kw)[0]


@pytest.fixture(scope="module")
def three_ops_step():
    from incubator_mxnet_tpu.parallel import TrainStep, make_mesh
    net = _ThreeOps()
    net.initialize()
    return TrainStep(
        net, lambda out, label: jnp.mean(out.astype(jnp.float32) * label),
        optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        mesh=make_mesh({"dp": 4}, jax.devices()[:4]),
        example_inputs=[mx.nd.ones((8, 3, 8, 8))])


@pytest.fixture(scope="module", params=["cpu", "v5e:2x2"])
def bn_all_reduces(request, three_ops_step):
    """(scope, backward?, elements) of every all-reduce under a BatchNorm
    scope in the step compiled for four devices: four of the suite's CPU
    devices, or the four described chips of a v5e:2x2."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    devices = jax.devices()[:4] if request.param == "cpu" \
        else request.getfixturevalue("v5e").devices[:4]
    mesh = Mesh(np.array(devices), ("dp",))
    rep, dat = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    step = three_ops_step

    def aval(v, sharding):
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sharding)
    text = jax.jit(step._step_fn).lower(
        {k: aval(v, rep) for k, v in step.params.items()},
        {k: tuple(aval(s, rep) for s in st)
         for k, st in step.opt_state.items()},
        aval(jax.random.PRNGKey(0), rep), 0,
        jax.ShapeDtypeStruct((8, 3, 8, 8), jnp.float32, sharding=dat),
        jax.ShapeDtypeStruct((8, _BN_C, 8, 8), jnp.float32, sharding=dat),
    ).compile().as_text()
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) all-reduce(?:-start)?\(",
                     line)
        if not m:
            continue
        name = re.search(r'op_name="([^"]*)"', line).group(1)
        scope = [part for part in name.split("/") if part in _BN_LAYERS]
        # the step's one gradient all-reduce is named after any of its
        # members, gamma's and beta's among them: known by a conv's weight
        if scope and not re.search(r"\[8,[38],3,3\]", m.group(1)):
            found.append((scope[0], "transpose(" in name, sum(
                int(np.prod([int(d) for d in dims.split(",") if d]))
                for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(1)))))
    return found


@pytest.mark.parametrize("scope", sorted(_BN_LAYERS))
def test_batchnorm_on_a_dp_mesh_sends_only_its_statistics(bn_all_reduces,
                                                          scope):
    """No all-reduce of a BatchNorm layer carries more than two f32[C]
    vectors; a layer's forward pass has at most two and its backward pass
    one (none where XLA lets the first layer's ride the gradient's)."""
    mine = [(back, n) for s, back, n in bn_all_reduces if s == scope]
    assert mine and max(n for _, n in mine) <= 2 * _BN_C, mine
    layers = _BN_LAYERS[scope]
    assert layers <= sum(not back for back, _ in mine) <= 2 * layers, mine
    assert sum(back for back, _ in mine) <= layers, mine
