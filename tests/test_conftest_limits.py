"""The suite's own bounds (tests/conftest.py): the per-test time limit
and the one helper that runs a module or script in a subprocess. Both
are exercised through one inner pytest, which loads this repo's conftest
as a plugin over a test file written to a temporary directory."""

import json
import os

import pytest

from conftest import SUBPROCESS_MARGIN_S, run_python_within

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))

_CHILD = """\
import os, subprocess, sys, time
g = subprocess.Popen([sys.executable, '-c', 'import time; time.sleep(120)'])
open({pids!r}, 'w').write(f'{{os.getpid()}} {{g.pid}}')
time.sleep(120)
"""

_INNER = """\
import time, pytest
from conftest import SUBPROCESS_MARGIN_S

@pytest.mark.time_limit(0.5, reason='the limit under test')
def test_sleeps_past_it():
    time.sleep(30)

def test_after_it():
    pass

@pytest.mark.time_limit(5)
def test_longer_limit_without_a_reason():
    pass

@pytest.mark.time_limit(SUBPROCESS_MARGIN_S + 1.5,
                        reason='a 1.5 s subprocess budget')
def test_waits_on_a_sleeper(run_python):
    run_python('-c', {child!r})
"""


@pytest.fixture(scope="module")
def inner(tmp_path_factory):
    """The inner run's ``CompletedProcess`` and the file in which its
    sleeper left its own pid and its child's."""
    tmp = tmp_path_factory.mktemp("inner")
    pids = tmp / "pids"
    (tmp / "pytest.ini").write_text("[pytest]\n")
    (tmp / "test_inner.py").write_text(
        _INNER.format(child=_CHILD.format(pids=str(pids))))
    proc = run_python_within(
        120.0, "-m", "pytest", "-p", "conftest", "-p", "no:cacheprovider",
        "-p", "no:xdist", "-p", "no:randomly", "-q",
        str(tmp / "test_inner.py"),
        env={"PYTHONPATH": TESTS_DIR + os.pathsep +
             os.path.dirname(TESTS_DIR)})
    assert "2 failed, 1 passed, 1 error" in proc.stdout, \
        proc.stdout + proc.stderr
    return proc, pids


def _gone(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] == "Z"
    except FileNotFoundError:
        return True


def test_a_test_past_its_limit_fails_by_name_and_the_next_runs(inner):
    out = inner[0].stdout
    assert "test_inner.py::test_sleeps_past_it ran past its time limit " \
        "of 0.5 s" in out
    # faulthandler's dump of every thread, the sleeping line among them
    assert "most recent call first" in out
    assert "in test_sleeps_past_it" in out


def test_a_longer_limit_needs_its_reason(inner):
    assert "time_limit takes the seconds and a reason=" in inner[0].stdout


def test_the_helper_kills_child_and_grandchild_at_its_timeout(inner):
    proc, pids = inner
    assert "was killed after 1.5 s" in proc.stdout, proc.stdout
    child_pid, grandchild_pid = map(int, pids.read_text().split())
    assert _gone(child_pid) and _gone(grandchild_pid)


def test_the_helper_scrubs_the_environment(run_python, monkeypatch):
    monkeypatch.setenv("ZOO_TPU_FAULT", "step:kill@1")
    monkeypatch.setenv("ZOO_HOSTDEV_CHILD", "1")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    proc = run_python("-c", "import json, os; "
                      "print(json.dumps(dict(os.environ)))",
                      env={"ZOO_TPU_TELEMETRY": "1"})
    env = json.loads(proc.stdout)
    # what the caller asks for by name is the only ZOO_* name left
    assert [k for k in env if k.startswith("ZOO_")] == ["ZOO_TPU_TELEMETRY"]
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["PYTHONPATH"].split(os.pathsep)[0] == \
        os.path.dirname(TESTS_DIR)
