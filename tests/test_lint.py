"""mxlint self-tests: the tier-1 self-clean gate.

Three layers: (1) every rule id fires on its known-bad corpus fixture and
stays quiet on the matching clean one, (2) the shipped package lints clean
with the suppression budget asserted exactly, (3) the CLI contract
(--format=json, exit codes, --changed).  Plus regression tests for the
true positives the first lint run surfaced (PR 4 cleanup sweep).

The lint layers never import incubator_mxnet_tpu — mxlint is pure stdlib
ast, so these tests run in milliseconds with no jax involved.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.mxlint import RULES, lint_paths, lint_source  # noqa: E402

CORPUS = os.path.join(REPO, "tests", "fixtures", "lint_corpus")
PKG = os.path.join(REPO, "incubator_mxnet_tpu")

# the whole-package suppression budget, asserted EXACTLY: adding a
# suppression means updating this list (and defending it in review).
# ISSUE-4 policy: at most 10 in-tree, each with a reason.
EXPECTED_SUPPRESSIONS = [
    ("TS03", "incubator_mxnet_tpu/gluon/block.py"),
]


def _run_cli(args, cwd=REPO, env=None):
    return subprocess.run([sys.executable, "-m", "tools.mxlint"] + args,
                          capture_output=True, text=True, cwd=cwd, env=env)


# -- corpus ----------------------------------------------------------------

@pytest.mark.parametrize("rule", sorted(RULES))
def test_rule_fires_on_bad_fixture(rule):
    res = lint_paths([os.path.join(CORPUS, f"bad_{rule.lower()}.py")])
    fired = {f.rule for f in res.findings}
    assert rule in fired, f"{rule} did not fire on its bad fixture"
    # fixtures are precise: nothing else may fire on them
    assert fired == {rule}, f"extra rules fired: {sorted(fired - {rule})}"
    assert not res.errors


@pytest.mark.parametrize("rule", sorted(RULES))
def test_rule_quiet_on_ok_fixture(rule):
    res = lint_paths([os.path.join(CORPUS, f"ok_{rule.lower()}.py")])
    assert [f.render() for f in res.findings] == []
    assert not res.errors


def test_findings_carry_location_and_hint():
    res = lint_paths([os.path.join(CORPUS, "bad_ev01.py")])
    f = res.findings[0]
    assert f.path.endswith("bad_ev01.py") and f.line > 0 and f.hint
    assert f.rule in RULES


# -- the package self-clean gate -------------------------------------------

def test_package_lints_clean():
    res = lint_paths([PKG])
    assert res.files_scanned > 100
    assert [f.render() for f in res.findings] == []
    assert not res.errors


def test_suppression_budget_exact():
    res = lint_paths([PKG])
    got = [(f.rule, f.path) for f in res.suppressed]
    assert got == EXPECTED_SUPPRESSIONS
    assert len(got) <= 10, "ISSUE-4 budget: at most 10 in-tree suppressions"
    for f in res.suppressed:
        assert f.suppress_reason and f.suppress_reason.strip(), \
            "every suppression must carry a reason"


# -- suppression semantics -------------------------------------------------

def test_suppression_needs_reason():
    src = ('import os\n'
           'x = os.environ.get("MXNET_X")  # mxlint: disable=EV01()\n')
    findings, suppressed = lint_source(src)
    assert [f.rule for f in findings] == ["EV01"]
    assert suppressed == []


def test_suppression_with_reason_counted():
    src = ('import os\n'
           '# mxlint: disable=EV01(corpus exercise)\n'
           'x = os.environ.get("MXNET_X")\n')
    findings, suppressed = lint_source(src)
    assert findings == []
    assert [(f.rule, f.suppress_reason) for f in suppressed] == \
        [("EV01", "corpus exercise")]


def test_suppression_wrong_rule_does_not_silence():
    src = ('import os\n'
           'x = os.environ.get("MXNET_X")  # mxlint: disable=TS01(nope)\n')
    findings, _ = lint_source(src)
    assert [f.rule for f in findings] == ["EV01"]


def test_cc04_timed_waits_pass_untimed_fire():
    src = ('import threading\n'
           '_lock = threading.Lock()\n'
           'def go(t):\n'
           '    with _lock:\n'
           '        t.join(timeout=1.0)\n'
           '    with _lock:\n'
           '        t.join()\n')
    findings, _ = lint_source(src)
    assert [(f.rule, f.line) for f in findings] == [("CC04", 7)]


def test_cc04_blocking_ok_leaf_allowance():
    # the same subprocess-under-lock body fires in an unregistered
    # module but is allowed at native/__init__.py, whose module lock is
    # a reviewed BLOCKING_OK entry (single-flight native build)
    src = ('import subprocess\n'
           'import threading\n'
           '_lock = threading.Lock()\n'
           'def build(cmd):\n'
           '    with _lock:\n'
           '        subprocess.run(cmd, timeout=120)\n')
    findings, _ = lint_source(src)
    assert [f.rule for f in findings] == ["CC04"]
    findings, _ = lint_source(
        src, path="incubator_mxnet_tpu/native/__init__.py")
    assert findings == []


# -- CLI contract ----------------------------------------------------------

def test_cli_json_clean_on_package():
    p = _run_cli(["incubator_mxnet_tpu", "--format=json"])
    assert p.returncode == 0, p.stdout + p.stderr
    data = json.loads(p.stdout)
    assert data["findings"] == []
    assert data["errors"] == []
    assert data["files_scanned"] > 100
    assert len(data["suppressed"]) == len(EXPECTED_SUPPRESSIONS)
    assert all(s["reason"] for s in data["suppressed"])


def test_cli_exit_1_on_findings():
    p = _run_cli([os.path.join(CORPUS, "bad_ev01.py")])
    assert p.returncode == 1
    assert "EV01" in p.stdout and "hint:" in p.stdout


def test_cli_exit_2_on_missing_path():
    p = _run_cli(["no/such/dir"])
    assert p.returncode == 2


def test_cli_changed_mode(tmp_path):
    """--changed lints exactly the files modified vs HEAD (plus
    untracked), exercised in a throwaway git repo."""
    env = dict(os.environ, PYTHONPATH=REPO,
               GIT_AUTHOR_NAME="t", GIT_AUTHOR_EMAIL="t@t",
               GIT_COMMITTER_NAME="t", GIT_COMMITTER_EMAIL="t@t")
    repo = str(tmp_path)

    def git(*args):
        subprocess.run(["git"] + list(args), cwd=repo, check=True,
                       capture_output=True, env=env)

    git("init", "-q")
    clean = 'VALUE = 1\n'
    with open(os.path.join(repo, "mod.py"), "w") as f:
        f.write(clean)
    git("add", "."); git("commit", "-qm", "seed")

    p = _run_cli(["--changed"], cwd=repo, env=env)
    assert p.returncode == 0, p.stdout + p.stderr

    with open(os.path.join(repo, "mod.py"), "w") as f:
        f.write('import os\nVALUE = os.environ.get("MXNET_BAD_KNOB")\n')
    with open(os.path.join(repo, "untracked.py"), "w") as f:
        f.write(clean)
    p = _run_cli(["--changed"], cwd=repo, env=env)
    assert p.returncode == 1
    assert "EV01" in p.stdout and "mod.py" in p.stdout


def test_cli_changed_mode_follows_renames(tmp_path):
    """--changed lints a renamed-then-edited file at its NEW path even
    when the repo config disables rename detection: the -M
    --name-status parse keys off the last tab field, and D rows (the
    old name) are skipped instead of relying on path existence."""
    env = dict(os.environ, PYTHONPATH=REPO,
               GIT_AUTHOR_NAME="t", GIT_AUTHOR_EMAIL="t@t",
               GIT_COMMITTER_NAME="t", GIT_COMMITTER_EMAIL="t@t")
    repo = str(tmp_path)

    def git(*args):
        subprocess.run(["git"] + list(args), cwd=repo, check=True,
                       capture_output=True, env=env)

    git("init", "-q")
    # rename detection off in config: -M in the lint command must still
    # force it, so the R row carries old AND new names
    git("config", "diff.renames", "false")
    body = "VALUE = 1\n" + "# filler\n" * 12
    with open(os.path.join(repo, "old_name.py"), "w") as f:
        f.write(body)
    git("add", "."); git("commit", "-qm", "seed")
    git("mv", "old_name.py", "new_name.py")
    with open(os.path.join(repo, "new_name.py"), "w") as f:
        f.write('import os\nV = os.environ.get("MXNET_BAD_KNOB")\n'
                + "# filler\n" * 12)

    p = _run_cli(["--changed"], cwd=repo, env=env)
    assert p.returncode == 1, p.stdout + p.stderr
    assert "EV01" in p.stdout and "new_name.py" in p.stdout
    assert "old_name.py" not in p.stdout

    # a pure rename (no edit) of a clean file stays clean — the R row
    # parse must not crash on the three-field form
    git("add", "."); git("commit", "-qm", "renamed")
    git("mv", "new_name.py", "third_name.py")
    p = _run_cli(["--changed"], cwd=repo, env=env)
    assert p.returncode == 1, "the violation rides along at third_name.py"
    assert "third_name.py" in p.stdout


# -- regression tests for the first-run true positives ---------------------

def test_argext_split_predicate_is_shape_based():
    """argmax/argmin's >=2^31 split branch takes the static shape tuple
    (was: the traced array — mxlint TS02 on the first package run)."""
    from incubator_mxnet_tpu.ops.tensor_ops import _argext_needs_split
    assert _argext_needs_split((2**31,), None)
    assert _argext_needs_split((2, 2**30), None)
    assert not _argext_needs_split((2, 2**30), 0)
    assert _argext_needs_split((2, 2**31), 1)
    assert _argext_needs_split((2, 2**31), -1)
    assert not _argext_needs_split((4, 4), None)


def test_getenv_helpers_semantics(monkeypatch):
    """util.getenv_* read through ENV_VARS: declared defaults, garbage
    int falls back (preserves the old profiler behavior), bool falsy
    spellings, undeclared name raises."""
    from incubator_mxnet_tpu import util
    from incubator_mxnet_tpu.base import MXNetError
    monkeypatch.delenv("MXNET_COMPILE_WARN_THRESHOLD", raising=False)
    assert util.getenv_int("MXNET_COMPILE_WARN_THRESHOLD") == 8
    monkeypatch.setenv("MXNET_COMPILE_WARN_THRESHOLD", "not-an-int")
    assert util.getenv_int("MXNET_COMPILE_WARN_THRESHOLD") == 8
    monkeypatch.setenv("MXNET_COMPILE_WARN_THRESHOLD", "3")
    assert util.getenv_int("MXNET_COMPILE_WARN_THRESHOLD") == 3
    for falsy in ("", "0", "false", "OFF", "No"):
        monkeypatch.setenv("MXTPU_NO_NATIVE", falsy)
        assert util.getenv_bool("MXTPU_NO_NATIVE") is False
    monkeypatch.setenv("MXTPU_NO_NATIVE", "1")
    assert util.getenv_bool("MXTPU_NO_NATIVE") is True
    monkeypatch.delenv("MXTPU_FP32_MATMUL", raising=False)
    assert util.getenv_str("MXTPU_FP32_MATMUL") == "strict"
    with pytest.raises(MXNetError):
        util.getenv_int("MXNET_NEVER_DECLARED")
    # the registry itself is complete: every entry has kind + doc
    for name, spec in util.ENV_VARS.items():
        assert name.startswith(("MXNET_", "MXTPU_"))
        assert spec.kind in ("int", "bool", "str") and spec.doc
    # the knob count only goes down (ROADMAP D3); jax's compilation cache
    # is placed by JAX_COMPILATION_CACHE_DIR, not a knob of ours
    assert len(util.ENV_VARS) <= 84
    assert "MXTPU_COMPILE_CACHE" not in util.ENV_VARS


def test_env_registry_matches_ast_extraction():
    """The registry mxlint recovers by PARSING util.py equals the one the
    runtime sees — guards against the linter and the package drifting."""
    from tools.mxlint.rules_env import load_registry
    from incubator_mxnet_tpu import util
    parsed = load_registry(PKG)
    assert parsed == set(util.ENV_VARS)
