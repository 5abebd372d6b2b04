"""chip_smoke.py on a machine without a chip, and the compile-cache rule.

The smoke itself only means something on the TPU; what the CPU can hold
it to is its contract around that: no accelerator -> non-zero exit within
seconds, naming the platform, no result line, no leg run; the rehearsal
mode (never the default, loudly labelled) drives all three legs at tiny
sizes so the script cannot rot between chip runs; and jax's compilation
cache sits where JAX_COMPILATION_CACHE_DIR says, else in the checkout.
"""
import json
import os
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd=REPO, timeout=600, **env):
    full = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=timeout)


def _no_result_line(stdout):
    for line in stdout.splitlines():
        if line.startswith("{"):
            assert "ok" not in json.loads(line), line


def test_no_tpu_is_a_fast_failure_that_names_the_platform():
    t0 = time.monotonic()
    r = _run([SMOKE])
    assert time.monotonic() - t0 < 60
    assert r.returncode != 0
    out = r.stdout + r.stderr
    assert "no TPU" in out and "platform 'cpu'" in out
    # the device is the first thing said, and no leg got past it
    assert r.stdout.startswith("[smoke:train] jax ")
    assert "platform=cpu" in r.stdout.splitlines()[0]
    assert "losses" not in out and "PASSED" not in out
    _no_result_line(r.stdout)


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _run([str(tmp_path / "chip_smoke.py")], cwd=str(tmp_path))
    assert r.returncode != 0
    _no_result_line(r.stdout)


def test_rehearsal_drives_all_three_legs(tmp_path):
    cache = tmp_path / "jaxcache"
    r = _run([SMOKE, "--rehearse"], JAX_COMPILATION_CACHE_DIR=str(cache))
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    assert r.stdout.splitlines()[1].startswith("REHEARSAL")
    last = json.loads(r.stdout.strip().splitlines()[-1])
    # a rehearsal can never be mistaken for the check
    assert last["rehearsal"] is True and "ok" not in last
    assert last["legs_passed"] == ["train", "lm", "serve"]
    assert last["device"]["platform"] == "cpu"
    for leg in last["legs_passed"]:
        assert f"[smoke:{leg}] PASSED" in r.stdout
    # four virtual devices: the multi-chip layout code ran too
    assert last["device"]["count"] == 4
    assert "[smoke:lm] mesh {'dp': 2, 'tp': 2}" in r.stdout
    assert "equal to the sequential greedy decode" in r.stdout
    # and the cache went where the environment said
    assert f"jax compilation cache {cache}:" in r.stdout
    assert os.listdir(cache)


_WHERE = ("import jax, incubator_mxnet_tpu; "
          "print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_is_placed_from_outside(tmp_path):
    r = _run(["-c", _WHERE], JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == str(tmp_path)


def test_compile_cache_defaults_into_the_checkout():
    r = _run(["-c", _WHERE])
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == \
        os.path.join(REPO, ".jax_cache")
