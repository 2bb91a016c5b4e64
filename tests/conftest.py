"""Test-suite bootstrap: a hard time limit per test and lock-order watchdog
fixtures."""

import faulthandler
import signal

import pytest

# -- per-test time limit --------------------------------------------------------
#: seconds one test may run: about 3x the slowest test in the suite
#: (``test_train_integration.py::test_checkpoint_restart_resumes_identically``,
#: 44 to 48 s under ``-n 6``). A test past it fails with every thread's stack
#: on its captured stderr; the rest of its file still runs.
TEST_TIME_LIMIT = 150


def _over_time(signum, frame):
    faulthandler.dump_traceback(all_threads=True)
    pytest.fail(
        f"test exceeded its {TEST_TIME_LIMIT} s limit "
        "(every thread's stack is on its captured stderr)",
        pytrace=False,
    )


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    # SIGALRM interrupts the main thread even inside Thread.join or a sleep
    previous = signal.signal(signal.SIGALRM, _over_time)
    signal.alarm(TEST_TIME_LIMIT)
    try:
        return (yield)
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, previous)


# -- lockwatch integration ----------------------------------------------------
# With REPRO_LOCKWATCH=1 every core lock is a WatchedLock reporting to the
# process-global acquisition graph; these fixtures (no-ops otherwise) install
# the join-under-lock hooks once and fail any test that recorded a violation.


@pytest.fixture(scope="session", autouse=True)
def _lockwatch_hooks():
    from repro.analysis import lockwatch

    if lockwatch.enabled():
        lockwatch.install_blocking_hooks()
    yield


@pytest.fixture(autouse=True)
def _lockwatch_assert_clean():
    yield
    from repro.analysis import lockwatch

    if lockwatch.enabled():
        lockwatch.watch().assert_clean(reset=True)
