"""Pytest config: smoke tests run on the single real CPU device.

Multi-device tests (tests/test_distributed.py, test_context_parallel.py)
spawn subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count=N
so this process never locks a fake device count (per spec).

``hypothesis`` is optional (unavailable in offline containers): the
property-test modules importorskip it themselves, and the profile below
is only registered when the package is importable.

Every test's setup and call run under a per-test limit
(``subproc.TEST_LIMIT_S``): a test that runs past it fails under its own
name instead of eating the whole suite's clock.  The limit's signal is
handled only once the main thread is back in Python, so a test stuck in
native code (a compile, a collective) is not stopped by it; 30 s later
its stacks are printed to stderr instead.

``REPRO_TEST_TIMES=<file>`` appends one tab-separated line per test phase
(nodeid, phase, seconds, outcome) as each phase ends, so a run that is cut
still shows where its time went.
"""

import faulthandler
import os
import signal
import sys
import threading

import pytest

from subproc import TEST_LIMIT_S

try:
    # keep hypothesis deadlines off for jit-compiling properties
    from hypothesis import settings
except ImportError:
    settings = None

if settings is not None:
    settings.register_profile("repro", deadline=None, derandomize=True)
    settings.load_profile("repro")


def pytest_report_header(config):
    import jax

    return f"jax devices: {jax.device_count()} ({jax.devices()[0].platform})"


class TimeLimitExceeded(BaseException):
    """A test phase ran past the per-test limit.

    A ``BaseException``, so that code under test which catches
    ``Exception`` (the guarded dispatch's fallback chain) cannot swallow it.
    """


_stderr_fd = None


def pytest_configure(config):
    # stderr as it is outside any test's captured output
    global _stderr_fd
    _stderr_fd = os.dup(sys.__stderr__.fileno())


def _limited(item, phase):
    """Run one test phase under SIGALRM; yields to the hook wrapper."""
    if threading.current_thread() is not threading.main_thread():
        return (yield)  # signals reach the main thread only

    def expire(signum, frame):
        raise TimeLimitExceeded(
            f"{item.nodeid} {phase} ran past the per-test limit of {TEST_LIMIT_S:g} s"
        )

    old = signal.signal(signal.SIGALRM, expire)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    faulthandler.dump_traceback_later(TEST_LIMIT_S + 30, file=_stderr_fd)
    try:
        return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    return (yield from _limited(item, "setup"))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    return (yield from _limited(item, "call"))


def pytest_runtest_logreport(report):
    path = os.environ.get("REPRO_TEST_TIMES")
    # under xdist the controller re-fires each worker's report with a
    # ``node`` attribute; only the process that ran the test writes
    if not path or hasattr(report, "node"):
        return
    with open(path, "a") as f:
        f.write(f"{report.nodeid}\t{report.when}\t{report.duration:.3f}\t{report.outcome}\n")
