"""The suite's own guards in conftest.py — the per-test time limit and the
per-phase timing log — exercised on a throwaway suite in a child pytest,
whose limit is 1 s."""

import os
import shutil
import subprocess
import sys

import pytest

from subproc import SUBPROCESS_TIMEOUT_S

HERE = os.path.dirname(os.path.abspath(__file__))


def run_inner_suite(tmp_path, test_src, *args, **env):
    """Run ``test_src`` as ``test_inner.py`` under this suite's conftest."""
    shutil.copy(os.path.join(HERE, "conftest.py"), tmp_path / "conftest.py")
    (tmp_path / "subproc.py").write_text("TEST_LIMIT_S = 1.0\n")
    (tmp_path / "test_inner.py").write_text(test_src)
    inner_env = {k: v for k, v in os.environ.items() if k != "REPRO_TEST_TIMES"}
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider", *args,
         "test_inner.py"],
        cwd=tmp_path, env=dict(inner_env, **env), capture_output=True, text=True,
        timeout=SUBPROCESS_TIMEOUT_S,
    )


XDIST = pytest.mark.parametrize(
    "xdist", [[], ["-p", "xdist", "-n", "1"]], ids=["serial", "xdist"]
)


@XDIST
def test_limit_fails_a_test_that_runs_past_it(tmp_path, xdist):
    src = (
        "import time\n\n"
        "def test_sleeps():\n    time.sleep(60)\n\n"
        "def test_quick():\n    pass\n"
    )
    proc = run_inner_suite(tmp_path, src, *xdist)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 1, out
    assert "1 failed, 1 passed" in out
    assert "test_inner.py::test_sleeps call ran past the per-test limit of 1 s" in out


@XDIST
def test_timing_hook_writes_each_phase(tmp_path, xdist):
    log = tmp_path / "times.tsv"
    proc = run_inner_suite(
        tmp_path, "def test_trivial():\n    pass\n", *xdist, REPRO_TEST_TIMES=str(log)
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rows = [line.split("\t") for line in log.read_text().splitlines()]
    assert [(r[0], r[1], r[3]) for r in rows] == [
        ("test_inner.py::test_trivial", phase, "passed")
        for phase in ("setup", "call", "teardown")
    ]
    assert all(float(r[2]) >= 0.0 for r in rows)
