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
    r"/rematted_computation/attn/flash_fwd/",
    r"/attn/flash_bwd_dq/",
    r"/attn/flash_bwd_dkv/",
])
def test_lm_step_operations_carry_the_programs_names(lm_names, pattern):
    assert any(re.search(pattern, n) for n in lm_names), \
        sorted(lm_names)[:40]


@pytest.mark.parametrize("which", ["trainstep_names", "lm_names"])
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
def one_chip():
    import os
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — whatever libtpu raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("kernels", [("flash_fwd",),
                                     ("flash_bwd_dq", "flash_bwd_dkv")])
def test_flash_kernels_keep_their_names_in_a_tpu_program(one_chip, kernels):
    """GPT-2 medium's shapes, (BH, T, D) = (512, 1024, 64) in 1024 x 1024
    blocks: each kernel is ONE custom call whose instruction and `op_name`
    carry the kernel's name under the caller's scopes. `flash_time_share`
    reads the opcode, `flash_*_roofline` the name and the operand shapes."""
    import importlib
    fa = importlib.import_module(     # the package exports a function by
        "incubator_mxnet_tpu.parallel.flash_attention")     # the same name
    big = jax.ShapeDtypeStruct((512, 1024, 64), jnp.bfloat16,
                               sharding=one_chip)
    row = jax.ShapeDtypeStruct((512, 1024, 1), jnp.float32,
                               sharding=one_chip)

    def fwd(q, k, v):
        with jax.named_scope("forward"), jax.named_scope("attn"):
            return fa._fa_forward(q, k, v, True, 0.125, 1024, 1024, False)

    def bwd(q, k, v, do, lse, out, dlse):
        with jax.named_scope("forward"), jax.named_scope("attn"):
            return fa._fa_backward(q, k, v, do, lse, out, dlse, True, 0.125,
                                   1024, 1024, False)
    if kernels == ("flash_fwd",):
        lowered = jax.jit(fwd).lower(big, big, big)
    else:
        lowered = jax.jit(bwd).lower(big, big, big, big, row, big, row)
    text = lowered.compile().as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == len(kernels)
    for name, line in zip(kernels, calls):
        assert re.match(rf"\s*(ROOT )?%{name}(\.\d+)? = ", line), line[:200]
        assert re.search(rf'op_name="jit\(\w+\)/forward/attn/{name}/'
                         r'pallas_call"', line), line[-400:]
        assert "bf16[512,1024,64]" in line.split("custom-call(")[1]
