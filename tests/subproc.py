"""Time limits for the test suite and the runner for multi-device checks.

Every test runs under ``TEST_LIMIT_S`` (enforced in ``conftest.py``); a
subprocess started by a test waits at most ``SUBPROCESS_TIMEOUT_S``, so it
times out under its own name before the test's limit fires.

Multi-device checks run in a child with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` so the pytest
process never locks a fake device count.
"""

import os
import subprocess
import sys
import textwrap

TEST_LIMIT_S = 300.0
SUBPROCESS_TIMEOUT_S = 240

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Called outside jit, shard_map runs its body one primitive at a time, each
# compiled as its own program and re-traced on every call: one eager
# distributed sort takes minutes on fake CPU devices.  The tests compile
# each mapped body as one program instead, as it runs inside a jitted step.
# The guarded wrappers around it (fault injection, verification, fallback,
# telemetry) still run eagerly; tests/test_telemetry.py keeps an eager
# shard_map call.  ROADMAP Speed 7: once core/distributed.py jits its
# mapped bodies itself, this wrapper and its two users go.
def jit_shard_map(eager_shard_map):
    """``shard_map`` whose result is compiled as one program."""
    import jax

    return lambda f, **kw: jax.jit(eager_shard_map(f, **kw))


# The same wrapping in a child started by run_with_devices.
JIT_SHARD_MAP = """
import repro.core.distributed as _dist
from subproc import jit_shard_map as _jit_shard_map
_dist.shard_map = _jit_shard_map(_dist.shard_map)
"""


def src_env(**extra) -> dict:
    """This process's environment with ``PYTHONPATH`` at the checkout's src."""
    return dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"), **extra)


def run_with_devices(code: str, n: int = 8, prelude: str = JIT_SHARD_MAP) -> str:
    """Run ``code`` in a child with ``n`` fake CPU devices; return its stdout.

    Fails the calling test on a non-zero exit, with the child's output.
    """
    env = src_env(XLA_FLAGS=f"--xla_force_host_platform_device_count={n}")
    env["PYTHONPATH"] += os.pathsep + os.path.dirname(os.path.abspath(__file__))
    env.pop("REPRO_FAULTS", None)  # scripts that inject faults name their own plans
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(prelude) + textwrap.dedent(code)],
        env=env, capture_output=True, text=True, timeout=SUBPROCESS_TIMEOUT_S,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    return proc.stdout
