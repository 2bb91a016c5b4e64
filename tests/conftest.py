"""Test-suite bootstrap: lock-order watchdog fixtures."""

import pytest

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
