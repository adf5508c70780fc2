"""mxtpu-lint: the tier-1 static-analysis gate plus the suite's own
contract tests.

Three layers:

1. **The gate** — ``python tools/mxtpu_lint.py mxnet_tpu tools`` must
   exit 0 against the committed baseline (tools/lint_baseline.json,
   kept EMPTY: every waiver in the tree is a per-line suppression with
   a reason, not a baseline entry).  This is what keeps the bug
   classes of PRs 2-6 from regrowing.
2. **Fixture tests** — for every checker, a ``*_bad.py`` fixture under
   tests/lint_fixtures/ reproduces the PRE-FIX shape of real code this
   PR cleaned up (it must produce findings) and a ``*_ok.py`` fixture
   carries the post-fix shape (it must be clean).  If a checker stops
   firing on its bad fixture, the gate has silently gone blind.
3. **Workflow tests** — suppression comments, the baseline round trip,
   and the check_env_docs regression pin (the env-docs drift gate from
   PR 5 survives its refactor onto the linter's scanner).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

from mxnet_tpu.lint import (LintContext, SourceFile, all_checkers,  # noqa: E402
                            apply_baseline, hot_path, load_baseline,
                            run_lint, save_baseline)

FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")

REQUIRED_CHECKERS = {
    "wall-clock", "host-sync", "jit-cache-capture", "use-after-donate",
    "env-discipline", "unlocked-shared-state", "swallowed-exception"}


def lint_fixture(name, checks=None):
    """Findings for one fixture file, linted against the REAL repo
    context (so documented env vars resolve)."""
    findings, errors = run_lint(
        [os.path.join(FIXTURES, name)], repo=REPO, checks=checks)
    assert not errors, errors
    return findings


def counts(findings):
    out = {}
    for f in findings:
        out[f.check] = out.get(f.check, 0) + 1
    return out


# -- 1. the tier-1 gate ------------------------------------------------------
def test_registry_has_all_required_checkers():
    assert REQUIRED_CHECKERS <= set(all_checkers())


def test_repo_is_lint_clean():
    """THE gate: zero non-baselined findings over mxnet_tpu/ + tools/.

    Run in-process (same linter the CLI wraps) so the failure message
    lists the findings directly."""
    findings, errors = run_lint(
        [os.path.join(REPO, "mxnet_tpu"), os.path.join(REPO, "tools")],
        repo=REPO)
    assert not errors, f"unparseable sources: {errors}"
    baseline = load_baseline(os.path.join(REPO, "tools",
                                          "lint_baseline.json"))
    new, _, stale = apply_baseline(findings, baseline)
    msg = "\n".join(f.render() for f in new)
    assert not new, f"new lint findings (fix or suppress with a " \
                    f"reason):\n{msg}"
    assert not stale, f"stale baseline entries (delete them): {stale}"


def test_cli_acceptance_invocation():
    """The acceptance-criteria command exits 0 and the JSON report is
    machine-readable."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mxtpu_lint.py"),
         "mxnet_tpu", "tools", "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["clean"] is True
    assert doc["findings"] == []
    assert REQUIRED_CHECKERS <= set(doc["checks"])


def test_baseline_is_empty_or_justified():
    """The committed baseline must stay empty — or every entry must
    carry a non-trivial 'why' (acceptance criterion)."""
    path = os.path.join(REPO, "tools", "lint_baseline.json")
    with open(path) as f:
        data = json.load(f)
    for e in data.get("entries", []):
        assert len(e.get("why", "").strip()) >= 10, \
            f"baseline entry without a justification: {e}"


# -- 2. per-checker fixtures (pre-fix shape fails, post-fix is clean) --------
@pytest.mark.parametrize("check,bad,expect_min", [
    ("wall-clock", "wall_clock_bad.py", 3),
    ("host-sync", "host_sync_bad.py", 3),
    ("jit-cache-capture", "jit_cache_capture_bad.py", 4),
    ("use-after-donate", "use_after_donate_bad.py", 3),
    ("env-discipline", "env_discipline_bad.py", 5),
    ("unlocked-shared-state", "unlocked_shared_state_bad.py", 2),
    ("swallowed-exception", "swallowed_exception_bad.py", 2),
])
def test_checker_fires_on_prefix_shape(check, bad, expect_min):
    found = counts(lint_fixture(bad, checks=[check]))
    assert found.get(check, 0) >= expect_min, \
        f"{check} went blind on {bad}: {found}"


@pytest.mark.parametrize("check,ok", [
    ("wall-clock", "wall_clock_ok.py"),
    ("host-sync", "host_sync_ok.py"),
    ("jit-cache-capture", "jit_cache_capture_ok.py"),
    ("use-after-donate", "use_after_donate_ok.py"),
    ("env-discipline", "env_discipline_ok.py"),
    ("unlocked-shared-state", "unlocked_shared_state_ok.py"),
    ("swallowed-exception", "swallowed_exception_ok.py"),
])
def test_checker_clean_on_postfix_shape(check, ok):
    found = lint_fixture(ok, checks=[check])
    msg = "\n".join(f.render() for f in found)
    assert not found, f"false positives on {ok}:\n{msg}"


def test_bad_fixtures_pinpoint_the_planted_lines():
    """Spot-check line anchoring: the wall-clock fixture's findings
    land on the exact time.time() lines."""
    lines = {f.line for f in lint_fixture("wall_clock_bad.py",
                                          checks=["wall-clock"])}
    src = open(os.path.join(FIXTURES, "wall_clock_bad.py")).read()
    expected = {i for i, l in enumerate(src.splitlines(), 1)
                if "time.time()" in l}
    assert lines == expected


# -- 3. suppression / baseline / hot_path workflow ---------------------------
def _lint_src(tmp_path, src, checks=None, name="snippet.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    findings, errors = run_lint([str(p)], repo=REPO, checks=checks)
    assert not errors, errors
    return findings


def test_suppression_same_line_and_line_above(tmp_path):
    base = """
    import time

    def f():
        return time.time()
    """
    assert len(_lint_src(tmp_path, base, ["wall-clock"])) == 1

    same_line = """
    import time

    def f():
        return time.time()  # mxtpu-lint: disable=wall-clock (ts)
    """
    assert _lint_src(tmp_path, same_line, ["wall-clock"]) == []

    line_above = """
    import time

    def f():
        # a multi-line waiver, the reason on its own line:
        # mxtpu-lint: disable=wall-clock (record timestamp for logs)
        return time.time()
    """
    assert _lint_src(tmp_path, line_above, ["wall-clock"]) == []


def test_suppression_disable_all_and_unrelated_check(tmp_path):
    src = """
    import time

    def f():
        return time.time()  # mxtpu-lint: disable=all (generated)
    """
    assert _lint_src(tmp_path, src, ["wall-clock"]) == []
    unrelated = """
    import time

    def f():
        return time.time()  # mxtpu-lint: disable=host-sync (wrong id)
    """
    assert len(_lint_src(tmp_path, unrelated, ["wall-clock"])) == 1


def test_baseline_round_trip(tmp_path):
    """findings -> write baseline -> re-run = clean; a NEW finding
    still fails; fixing the baselined line turns the entry stale."""
    p = tmp_path / "mod.py"
    p.write_text("import time\n\n"
                 "def f():\n    return time.time()\n")
    findings, _ = run_lint([str(p)], repo=REPO, checks=["wall-clock"])
    assert len(findings) == 1

    bl_path = tmp_path / "baseline.json"
    save_baseline(str(bl_path), findings, why="grandfathered for test")
    baseline = load_baseline(str(bl_path))
    new, matched, stale = apply_baseline(findings, baseline)
    assert new == [] and len(matched) == 1 and stale == []

    # a second offending line is NOT covered by the single-count entry
    p.write_text("import time\n\n"
                 "def f():\n    return time.time()\n\n"
                 "def g():\n    return time.time()\n")
    findings2, _ = run_lint([str(p)], repo=REPO, checks=["wall-clock"])
    new2, matched2, _ = apply_baseline(findings2, baseline)
    assert len(new2) == 1 and len(matched2) == 1

    # fixing the file leaves the baseline entry stale (reported so it
    # gets deleted — baselines shrink, never linger)
    p.write_text("import time\n\n"
                 "def f():\n    return time.perf_counter()\n")
    findings3, _ = run_lint([str(p)], repo=REPO, checks=["wall-clock"])
    new3, _, stale3 = apply_baseline(findings3, baseline)
    assert new3 == [] and len(stale3) == 1


def test_baseline_survives_line_drift(tmp_path):
    """Baseline entries key on (check, path, code), not line numbers —
    inserting lines above must not un-baseline a finding."""
    p = tmp_path / "mod.py"
    p.write_text("import time\n\ndef f():\n    return time.time()\n")
    findings, _ = run_lint([str(p)], repo=REPO, checks=["wall-clock"])
    bl_path = tmp_path / "baseline.json"
    save_baseline(str(bl_path), findings)
    p.write_text("import time\n\n# new comment\n# more lines\n\n"
                 "def f():\n    return time.time()\n")
    findings2, _ = run_lint([str(p)], repo=REPO, checks=["wall-clock"])
    new, matched, stale = apply_baseline(findings2,
                                         load_baseline(str(bl_path)))
    assert new == [] and len(matched) == 1 and stale == []


def test_hot_path_decorator_is_runtime_inert():
    @hot_path
    def fn(x):
        return x + 1

    assert fn(1) == 2
    assert getattr(fn, "__mxtpu_hot_path__") is True


def test_parse_error_is_loud(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def f(:\n")
    findings, errors = run_lint([str(p)], repo=REPO)
    assert findings == []
    assert len(errors) == 1 and "syntax error" in errors[0][1]


def test_guard_annotation_binds_to_its_own_line():
    src = ("import threading\n"
           "class S:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self.q = []   # guarded-by: _lock\n"
           "    def bad(self):\n"
           "        self.q = []\n"
           "    def ok(self):\n"
           "        with self._lock:\n"
           "            self.q = []\n")
    sf = SourceFile("s.py", src)
    chk = all_checkers()["unlocked-shared-state"]()
    found = list(chk.check(sf, LintContext(REPO)))
    assert [f.line for f in found] == [7]


# -- 4. env-docs drift gate regression (check_env_docs -> linter) ------------
def _fake_repo(tmp_path, code, docs):
    (tmp_path / "mxnet_tpu").mkdir()
    (tmp_path / "docs").mkdir()
    (tmp_path / "mxnet_tpu" / "mod.py").write_text(code)
    (tmp_path / "docs" / "env_vars.md").write_text(docs)
    return tmp_path


def test_env_docs_gate_previous_behavior_survives_refactor(tmp_path):
    """Pin check_env_docs.py's contract on the linter scanner: an
    undocumented MXTPU_* read fails, documenting it passes, and the
    linter's env-discipline checker reports the same drift."""
    import check_env_docs

    repo = _fake_repo(
        tmp_path,
        code="import os\nX = os.environ.get('MXTPU_SHINY_NEW_KNOB')\n",
        docs="| MXTPU_TELEMETRY | off | metrics |\n")
    missing, documented = check_env_docs.check(str(repo))
    assert set(missing) == {"MXTPU_SHINY_NEW_KNOB"}
    assert "MXTPU_TELEMETRY" in documented
    assert check_env_docs.main(["--repo", str(repo)]) == 1

    findings, _ = run_lint([str(repo / "mxnet_tpu")], repo=str(repo),
                           checks=["env-discipline"])
    assert any("MXTPU_SHINY_NEW_KNOB" in f.message for f in findings)

    # documenting the knob clears both faces of the gate
    (repo / "docs" / "env_vars.md").write_text(
        "| MXTPU_TELEMETRY | off | metrics |\n"
        "| MXTPU_SHINY_NEW_KNOB | - | new knob |\n")
    missing2, _ = check_env_docs.check(str(repo))
    assert missing2 == {}
    assert check_env_docs.main(["--repo", str(repo)]) == 0
    findings2, _ = run_lint([str(repo / "mxnet_tpu")], repo=str(repo),
                            checks=["env-discipline"])
    assert findings2 == []


def test_env_docs_real_repo_still_clean():
    import check_env_docs

    missing, documented = check_env_docs.check(REPO)
    assert missing == {}, f"undocumented MXTPU_* vars: {missing}"
    assert len(documented) >= 30
